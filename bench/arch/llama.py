"""The LLaMA-family decoder (Qwen2, DeepSeek-LLM) as the benchmark sees it:
the program's ``ModelConfig`` for a configuration file, and the operations
and bytes that the served model's steps need, from shapes.

A configuration file names this module by its ``"reference"`` key, which
also names its plain reference, ``bench/references/llama.py``. The files
of ``bench/configs`` use the published config's own key names.

The counts are the benchmark's own: what the mathematics of a step
requires, not what the program's current kernels happen to move. A later
change that drops a copy or a kernel cannot move them.

Conventions: a multiply-add is 2 FLOPs; weights and KV are read at the
served dtype's width (``Dims.wbytes`` / ``Dims.kvbytes``); activations
between layers are not counted as HBM traffic (they are small next to the
weights and KV at these shapes, and a fused step need not write them).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Sequence, Tuple


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


def counts(c: dict) -> "Dims":
    """The step counts of a configuration file (HF key names)."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    return Dims(layers=c["num_hidden_layers"], d_model=d, n_heads=h,
                n_kv_heads=c["num_key_value_heads"],
                d_head=c.get("head_dim") or d // h,
                d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                qkv_bias=bool(c["architecture"]["qkv_bias"]))


@dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab: int
    qkv_bias: bool
    wbytes: int = 2
    kvbytes: int = 2

    # ---------------------------------------------------------- parameters
    @property
    def attn_matmul_params(self) -> int:
        d, hq, hkv, dh = self.d_model, self.n_heads, self.n_kv_heads, self.d_head
        return d * hq * dh + 2 * d * hkv * dh + hq * dh * d

    @property
    def layer_matmul_params(self) -> int:
        return self.attn_matmul_params + 3 * self.d_model * self.d_ff

    @property
    def layer_params(self) -> int:
        bias = (self.n_heads + 2 * self.n_kv_heads) * self.d_head \
            if self.qkv_bias else 0
        return self.layer_matmul_params + bias + 2 * self.d_model

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab

    @property
    def kv_bytes_per_token(self) -> int:
        """K and V of one token in every layer."""
        return 2 * self.layers * self.n_kv_heads * self.d_head * self.kvbytes

    def weight_bytes(self) -> int:
        """Every weight the model holds: layers, embedding, head, final norm."""
        return self.wbytes * (self.layers * self.layer_params
                              + 2 * self.vocab * self.d_model + self.d_model)

    # ---------------------------------------------------------- per token
    def attn_flops(self, context: int) -> int:
        """Scores and weighted sum of one query token over ``context`` keys,
        all layers."""
        return 4 * self.layers * self.n_heads * self.d_head * context

    def token_matmul_flops(self) -> int:
        return 2 * self.layers * self.layer_matmul_params

    # ---------------------------------------------------------- per step
    def decode_step(self, contexts: Sequence[int]) -> Tuple[int, int]:
        """(FLOPs, bytes) of one decode step. ``contexts[i]`` is the number
        of keys sequence i attends over (its cached tokens plus the new one).

        Bytes: every layer weight, the head and the final norm read once,
        the embedding rows of the fed tokens, the live KV of each sequence
        at its real length, and the new token's KV written."""
        n = len(contexts)
        flops = sum(self.token_matmul_flops() + self.attn_flops(c)
                    + 2 * self.head_params for c in contexts)
        weights = self.wbytes * (self.layers * self.layer_params
                                 + self.head_params + self.d_model
                                 + n * self.d_model)
        kv_read = self.kv_bytes_per_token * sum(max(c - 1, 0)
                                                for c in contexts)
        kv_write = self.kv_bytes_per_token * n
        return flops, weights + kv_read + kv_write

    def prefill_chunk_flops(self, start: int, end: int, final: bool) -> int:
        """FLOPs of prefilling prompt positions [start, end): the layer
        matmuls for each token, causal attention over the prefix and the
        chunk, and the LM head for the last token only when the chunk is a
        prompt's final one (its logits give the first output token)."""
        n = end - start
        # token at position p attends over p + 1 keys
        ctx = (start + 1 + end) * n // 2
        flops = n * self.token_matmul_flops() + self.attn_flops(1) * ctx
        if final:
            flops += 2 * self.head_params
        return flops

    def step_flops(self, contexts: Iterable[int],
                   chunks: Iterable[Tuple[int, int, bool]]) -> int:
        """Useful FLOPs of one engine step: its decodes and its prefill
        chunks, with no padding."""
        f, _ = self.decode_step(list(contexts))
        return f + sum(self.prefill_chunk_flops(s, e, fin)
                       for s, e, fin in chunks)
