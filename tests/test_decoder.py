"""The five causal decoders on ``models.decoder``, at their cells' rehearsal
sizes on the CPU: what each block keeps across its recomputation comes from
its own mixer (``remat_keep``), or from Granite's ``remat_save`` where the
configuration gives one; the blocks sit in the parameter tree where the
benchmark's references read them; ``_config`` is the constructor's
keyword arguments."""

import inspect
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import analytics_zoo_tpu.nn as nn  # noqa: E402
from analytics_zoo_tpu.models import (AFMoE, GlmMoeLite,  # noqa: E402
                                      GraniteHybrid, Ouro, Qwen3Next)
from analytics_zoo_tpu.models.decoder import DecoderBlock  # noqa: E402
from benchmark.harness.manifest import merged  # noqa: E402

FLASH = ("flash_attention_out", "flash_attention_lse")
DELTA = ("gated_delta_rule_out", "gated_delta_rule_states",
         "gated_delta_rule_inverse")
SCAN = ("mamba2_ssd_out", "mamba2_ssd_states")

# configuration file -> (model, mixer of each block); Granite's blocks keep
# its remat_save, the flash pair, whichever their mixer
CASES = {
    "qwen3_next_80b_a3b_ep16": (Qwen3Next, ["gdn"] * 3 + ["attn"]),
    "trinity_mini_ep8": (AFMoE, ["attn"] * 5),
    "granite_4_0_h_micro_pp4": (GraniteHybrid, ["mamba"] * 9 + ["attn"]),
    "glm_4_7_flash_ep8": (GlmMoeLite, ["attn"] * 5),
    "ouro_2_6b_pp6": (Ouro, ["attn"] * 2),
}
KEEP = {"attn": FLASH, "gdn": DELTA, "mamba": ()}


def _rehearsal(name):
    with open(os.path.join(REPO, "benchmark/configs", name + ".json")) as f:
        config = json.load(f)
    return merged(config, config["rehearse"])["model"]


def _tree(model):
    ids = jnp.zeros((1, 16), jnp.int32)
    return jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids,
                                             training=True))["params"]


@pytest.mark.parametrize("name", list(CASES))
def test_each_block_keeps_what_its_own_mixer_tags(name):
    cls, mixers = CASES[name]
    kwargs = _rehearsal(name)
    model = cls(**kwargs)
    granite = cls is GraniteHybrid
    want = [FLASH if granite else KEEP[m] for m in mixers]
    assert [type(b) for b in model._blocks] == [nn.Remat] * len(mixers)
    assert [(b.name, b.inner.name, b.inner.mixer.name, b.save_names)
            for b in model._blocks] == [
        (f"remat_{i}", f"layer_{i}", m, w)
        for i, (m, w) in enumerate(zip(mixers, want))]
    params = _tree(model)
    for i, m in enumerate(mixers):
        assert m in params[f"remat_{i}"][f"layer_{i}"], (i, m)
    if cls is GlmMoeLite:  # the prediction module's block keeps its own
        remat = model._mtp.block
        assert (remat.name, remat.inner.name, remat.save_names) == (
            "remat", "block", FLASH)
        assert {"attn", "moe"} <= set(params["mtp"]["remat"]["block"])
    if granite:  # the one override: the scan's output and states kept
        scan = cls(**dict(kwargs, remat_save=list(SCAN)))
        assert {b.save_names for b in scan._blocks} == {SCAN}
    # without remat: the bare blocks, under their own names
    plain = cls(**dict(kwargs, remat=False))
    assert [(type(b), b.name) for b in plain._blocks] == [
        (DecoderBlock, f"layer_{i}") for i in range(len(mixers))]
    assert set(_tree(plain)) >= {f"layer_{i}" for i in range(len(mixers))}
    # _config: the constructor's keyword arguments, every one of them
    config = model._config
    assert set(config) == set(inspect.signature(cls).parameters)
    for key, value in kwargs.items():
        assert config[key] == (tuple(value) if key == "remat_save"
                               else value), key
    assert cls(**config)._config == config
