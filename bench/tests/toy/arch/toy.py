"""A stand-in architecture that only the tests know: a dense decoder under
key names of its own, counted by another rule (the MLP's matmuls and
weights alone). The harness and the metric readers take it unedited when
this directory is on ``bench.arch``'s path. ``CALLS`` records what they
asked of it."""
CALLS = []


def model_config(c: dict):
    from repro.configs.base import ModelConfig
    CALLS.append(("model_config", c["name"]))
    return ModelConfig(
        name=c["name"], family="dense", n_layers=c["depth"],
        d_model=c["width"], n_heads=c["heads"], n_kv_heads=c["kv_heads"],
        d_head=c["width"] // c["heads"], d_ff=c["mlp_width"],
        vocab_size=c["vocab"], block_pattern=("attn_mlp",), qkv_bias=False,
        rope_theta=c["theta"], act="silu", norm_eps=c["eps"],
        param_dtype="bfloat16", activation_dtype="bfloat16")


class MlpCounts:
    def __init__(self, c: dict):
        mlp = 3 * c["width"] * c["mlp_width"] * c["depth"]
        self.token_flops, self.weight_bytes = 2 * mlp, 2 * mlp

    def decode_step(self, contexts):
        CALLS.append(("decode_step", list(contexts)))
        return self.token_flops * len(contexts), self.weight_bytes

    def step_flops(self, contexts, chunks):
        CALLS.append(("step_flops", list(contexts), list(chunks)))
        tokens = len(list(contexts)) + sum(e - s for s, e, _ in chunks)
        return self.token_flops * tokens


def counts(c: dict) -> MlpCounts:
    CALLS.append(("counts", c["name"]))
    return MlpCounts(c)
