"""One module per model family, found by a configuration's ``family``.

Each gives: ``build(config)`` (the ``Module`` the system runs),
``loader(config, traffic, seed)`` (``load_sample(i, rng=None)`` for a
``StreamingDataFeed``), ``inputs(config, traffic, seed, n)`` (``n`` seeded
input rows), ``flops_per_sample(config, traffic)`` (training FLOPs the
forward and backward passes require, recomputation not counted),
``reference(config, variables, x)`` (the plain float32 ``jax.numpy`` forward
on the system's own parameter tree) and ``TOLERANCE`` with its reason."""

import importlib


def family(config):
    return importlib.import_module(f"benchmark.families.{config['family']}")
