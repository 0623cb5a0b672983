"""Whole served step: the model operations of every prompt admitted and
every token generated inside the traced slice, over the slice's length
times the chip's bf16 peak, in percent."""

from bench.harness.metric import model_flops_share


def read(run):
    return model_flops_share(run)
