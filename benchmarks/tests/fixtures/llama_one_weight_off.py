"""The fixture architecture with ONE weight changed on the reference's side:
the attention output projection of the last layer, its sign flipped. The
serving check has to come out as not correct."""

from . import llama_arch
from .llama_arch import (dims, kernel_costs, kv_block_bytes, make_loss,  # noqa: F401
                         program, train_flops_per_token, weight_bytes)


def make_logits(m: dict):
    ref = llama_arch.make_logits(m)

    def fn(params, tokens):
        return ref({**params, "w_o": params["w_o"].at[-1].multiply(-1.0)}, tokens)

    return fn
