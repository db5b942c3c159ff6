"""Device time per training step of the FFNs every token takes: the
shared experts (``repro.moe.shared``) and the leading dense layer
(``repro.mlp.dense``), in ms."""

from chipbench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "repro.moe.shared", "repro.mlp.dense")
