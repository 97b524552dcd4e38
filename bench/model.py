"""A configuration file as the program's ``ModelConfig``, and its weights.

The configuration files of ``bench/configs`` use the published config's
own key names. The weights are random, made from ``--seed`` on the device
in one jitted call, in the dtype they are served in: normal with the
published ``initializer_range`` for every weight and bias, norms at 1.
"""
from __future__ import annotations

import json
import pathlib
from typing import Any, Optional


CONFIG_DIR = pathlib.Path(__file__).resolve().parent / "configs"


def load_config(name: str, directory: Optional[pathlib.Path] = None) -> dict:
    return json.loads(((directory or CONFIG_DIR) / f"{name}.json").read_text())


def model_config(c: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    d, h = c["hidden_size"], c["num_attention_heads"]
    if c["hidden_act"] != "silu":
        raise ValueError(f"{c['name']}: hidden_act {c['hidden_act']!r}")
    return ModelConfig(
        name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
        d_model=d, n_heads=h, n_kv_heads=c["num_key_value_heads"],
        d_head=c.get("head_dim") or d // h, d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], block_pattern=("attn_mlp",),
        qkv_bias=bool(c["architecture"]["qkv_bias"]),
        rope_theta=float(c["rope_theta"]), act="silu",
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        param_dtype=c["torch_dtype"], activation_dtype=c["torch_dtype"])


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

