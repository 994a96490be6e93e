"""BERT encoder + vocabulary head (masked-LM shape: logits at every
position), as ``chip_smoke.py``'s ``BertMLM`` builds it from ``models.BERT``.

Departures of ``models.BERT`` from Devlin et al. that the reference follows,
because it checks the system and not the paper: pre-LN blocks with no final
LayerNorm, no biases on the q/k/v/o projections, tanh-approximated GELU,
LayerNorm epsilon 1e-6.  No width differs from ``bert-base-uncased``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import analytics_zoo_tpu.nn as nn
from analytics_zoo_tpu.models import BERT
from analytics_zoo_tpu.nn.module import Module

#: Largest |system - reference| over the reference's largest magnitude, at
#: the logits.  The system computes its blocks in bf16 (8 bits of mantissa,
#: 2^-9 = 2e-3 a rounding) through 12 layers and a 768-wide head; chip_smoke
#: holds its bf16 kernels to 2e-2 by the same measure.  A float32 system
#: lands near 1e-5, so a fall to a lower precision than stated (fp8: 2^-4)
#: cannot hide under it.
TOLERANCE = 2e-2


class BertMLM(Module):
    def __init__(self, model: dict):
        super().__init__()
        self.vocab = model["vocab_size"]
        self.bert = BERT(
            vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
            n_layers=model["n_layers"], n_heads=model["n_heads"],
            intermediate_mult=model["intermediate_mult"],
            max_position=model["max_position"], dropout=model["dropout"],
            use_flash=model["use_flash"],
            remat_attention=model["remat_attention"],
            dtype=jnp.dtype(model["dtype"]))

    def forward(self, scope, ids):
        h = scope.child(self.bert, ids, name="bert").astype(self.bert.dtype)
        return scope.child(nn.Dense(self.vocab), h, name="mlm_head")


def build(config: dict) -> Module:
    return BertMLM(config["model"])


def loader(config: dict, traffic: dict, seed: int):
    """Zipfian token ids, as text has: a few optimizer steps learn the
    frequent ones, so the loss on the repeating data falls visibly."""
    vocab, seq = config["model"]["vocab_size"], traffic["seq_len"]
    p = 1.0 / np.arange(1, vocab + 1)
    p /= p.sum()

    def load_sample(i: int, rng=None) -> dict:
        r = np.random.default_rng([seed, i])
        ids = r.choice(vocab, seq, p=p).astype(np.int32)
        return {"x": ids, "y": ids}

    return load_sample


def inputs(config: dict, traffic: dict, seed: int, n: int) -> np.ndarray:
    load = loader(config, traffic, seed)
    return np.stack([load(i)["x"] for i in range(n)])


def batch_spec(config: dict, traffic: dict):
    return (traffic["global_batch"], traffic["seq_len"]), np.int32


def flops_per_token(d_model: int, n_layers: int, seq: int, vocab: int,
                    hidden_mult: int = 4) -> float:
    """Training FLOPs a token: 6 x the matmul parameters (q/k/v/o and the
    two FFN matrices of each layer, and the vocabulary head; the embedding
    gather is no matmul) plus 12 x seq x d a layer for the forward and
    backward of the two T x T matmuls.  (Copied from bench.py.)"""
    per_layer = 4 * d_model * d_model + 2 * hidden_mult * d_model * d_model
    return 6.0 * (n_layers * per_layer + vocab * d_model) \
        + n_layers * 12.0 * seq * d_model


def flops_per_sample(config: dict, traffic: dict) -> float:
    m = config["model"]
    return traffic["seq_len"] * flops_per_token(
        m["hidden_size"], m["n_layers"], traffic["seq_len"],
        m["vocab_size"], m["intermediate_mult"])


def reference(config: dict, variables: dict, ids: np.ndarray) -> np.ndarray:
    """Plain float32 forward on the system's parameter tree."""
    m = config["model"]
    heads = m["n_heads"]

    def layer_norm(x, p):
        mean = x.mean(-1, keepdims=True)
        var = jnp.square(x - mean).mean(-1, keepdims=True)
        return (x - mean) / jnp.sqrt(var + 1e-6) * p["gamma"] + p["beta"]

    def forward(params, ids):
        b = params["bert"]
        t = ids.shape[1]
        x = b["tok_embed"]["embeddings"][ids] + b["pos_embed"][:, :t]
        x = layer_norm(x, b["embed_ln"])
        for i in range(m["n_layers"]):
            p = b[f"layer_{i}"]
            h = layer_norm(x, p["ln1"])
            split = lambda y: y.reshape(y.shape[:2] + (heads, -1))
            q, k, v = (split(h @ p["mha"][w]) for w in ("wq", "wk", "wv"))
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) \
                / jnp.sqrt(jnp.float32(q.shape[-1]))
            ctx = jnp.einsum("bhqk,bkhd->bqhd",
                             jax.nn.softmax(logits, axis=-1), v)
            x = x + ctx.reshape(x.shape) @ p["mha"]["wo"]
            h = layer_norm(x, p["ln2"])
            h = jax.nn.gelu(h @ p["ffn1"]["kernel"] + p["ffn1"]["bias"])
            x = x + h @ p["ffn2"]["kernel"] + p["ffn2"]["bias"]
        head = params["mlm_head"]
        return x @ head["kernel"] + head["bias"]

    params = jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), variables["params"])
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(forward)(params, jnp.asarray(ids)))
