"""Device milliseconds per traced tree under ``lgbm.gradients`` (where the
objective's gradients are traced inside a jit) and ``lgbm.quantize`` (int8
discretisation and its scales; on the exact path, packing the weights to bf16
hi/lo)."""

from chipbench import scope_reduce


def read(facts):
    return scope_reduce.part_ms_per_tree(facts, "grad_quant", __file__)
