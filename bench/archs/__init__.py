"""The architectures the benchmark serves, one module each.

A model in a configuration names its ``arch``; the module of that name
gives what the benchmark needs of the architecture:

* ``shapes(cfg)``: the parameter pytree, each leaf a tuple of ints, nested
  as the served program holds its parameters;
* ``forward(params, request, cfg, operands)``: the plain float32 reference,
  in ``jax.numpy`` and ``jax.lax`` alone, with matmul and conv operands
  rounded to ``operands`` (None: not rounded);
* ``flops(cfg)``: useful FLOPs per request at the published shapes;
* optionally ``INIT``: ``{leaf name: rule}`` for leaf names that
  ``reference.make_weights`` does not draw itself, each rule mapping the
  leaf's standard normal draw to its value;
* optionally ``requests(seed, cfg, n)``: the run's pool of ``n`` requests,
  each a pytree whose leaves have a leading batch axis of 1; by default
  ``inputs.pool(seed, cfg["img_size"], n)``, CT phantoms;
* optionally ``kernels(cfg)``: ``{name: (match, flops, bytes)}`` per
  request, ``match(module, op)`` selecting that kernel's device
  operations by executable and op name (``roofline.py``).

The module is ``bench/archs/<arch>.py``; an ``arch`` with no file there is
imported by its name from the module search path.
"""
from __future__ import annotations

import importlib
import pathlib

from .. import inputs

HERE = pathlib.Path(__file__).resolve().parent


def load(arch: str):
    """The module of architecture ``arch``."""
    return importlib.import_module(f"{__name__}.{arch}" if (HERE / f"{arch}.py").is_file() else arch)


def pools(models: list[dict], seed: int, n: int = inputs.POOL) -> list[list]:
    """One pool of ``n`` requests per model, made from the seed. Models
    that take the default requests at one ``img_size`` share one pool."""
    shared: dict = {}
    out = []
    for m in models:
        mod = load(m["arch"])
        if hasattr(mod, "requests"):
            pool = mod.requests(seed, m, n)
        else:
            if m["img_size"] not in shared:
                shared[m["img_size"]] = inputs.pool(seed, m["img_size"], n)
            pool = shared[m["img_size"]]
        if len(pool) != n:
            raise ValueError(f"{m['name']}: {len(pool)} requests in its pool, not {n}")
        out.append(pool)
    return out


def kernels(model: dict) -> dict:
    """``{name: (match, flops, bytes)}`` per request of the model's kernels; empty where its module names none."""
    mod = load(model["arch"])
    return mod.kernels(model) if hasattr(mod, "kernels") else {}
