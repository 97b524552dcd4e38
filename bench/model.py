"""A configuration file, the program's ``ModelConfig`` for it, and its weights.

A configuration file (``bench/configs/<config>.json``) names its
architecture by its ``"reference"`` key: ``bench/arch/<reference>.py``
turns the file into the program's ``ModelConfig`` and counts its steps,
and ``bench/references/<reference>.py`` is its plain reference. Nothing
here knows an architecture. The weights are random, made from ``--seed``
on the device in one jitted call, in the dtype they are served in: normal
with the published ``initializer_range`` for every weight and bias, norms
at 1.
"""
from __future__ import annotations

import importlib
import json
import pathlib
from typing import Any, Optional


CONFIG_DIR = pathlib.Path(__file__).resolve().parent / "configs"


def load_config(name: str, directory: Optional[pathlib.Path] = None) -> dict:
    return json.loads(((directory or CONFIG_DIR) / f"{name}.json").read_text())


def arch(c: dict):
    """The architecture module a configuration file names by its
    ``"reference"`` key, ``bench/arch/<reference>.py``: its
    ``model_config(c)`` and ``counts(c)``."""
    return importlib.import_module(f"bench.arch.{c['reference']}")


def seed_key(seed: int):
    """A PRNG key from any non-negative seed (more than 32 bits)."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def make_weights(cfg, std: float, seed: int) -> Any:
    """The parameter tree of ``cfg`` (shapes and dtypes from the program's
    ``model_specs``), made on the default device in one jitted call."""
    import jax
    import jax.numpy as jnp
    from repro.models import model_specs
    from repro.models.params import ParamSpec

    specs = model_specs(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 specs, is_leaf=lambda x: isinstance(x, ParamSpec))[0]]

    def build(key):
        out = []
        for i, (path, s) in enumerate(zip(paths, leaves)):
            if "ln" in path.rsplit("[", 1)[-1]:       # ln1, ln2, final_ln
                out.append(jnp.ones(s.shape, s.dtype))
                continue
            k = jax.random.fold_in(key, i)
            out.append((jax.random.normal(k, s.shape, jnp.float32) * std)
                       .astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(seed_key(seed))

