"""The fixture architecture pointed at the WRONG plain reference: the GPT
family's (LayerNorm, an MLP without its gate), over the same parameter tree.
The serving check has to come out as not correct."""

from benchmarks.arch import gpt

from .llama_arch import (dims, kernel_costs, kv_block_bytes, make_loss,  # noqa: F401
                         program, train_flops_per_token, weight_bytes)


def make_logits(m: dict):
    return gpt.make_logits({
        "n_layers": m["layers"], "d_model": m["hidden"], "n_heads": m["heads"],
        "d_head": m["head"], "pos": "rotary", "rotary_dim": m["head"],
        "parallel_block": False, "tie_embeddings": False})
