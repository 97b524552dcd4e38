"""The stand-in architecture's plain reference: the LLaMA-family
reference, given the toy configuration under that family's key names."""
from bench.references import llama


def served_gaps(params, cfg: dict, prompt, served, lowp: bool = False):
    hf = {"rms_norm_eps": cfg["eps"], "rope_theta": cfg["theta"],
          "num_hidden_layers": cfg["depth"]}
    return llama.served_gaps(params, hf, prompt, served, lowp=lowp)
