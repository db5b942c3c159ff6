"""Device time per training step of the routed experts: the router's
scores, selection, dispatch and combine (``repro.moe.route``) and the
held experts' matmuls (``repro.moe.experts``), in ms."""

from chipbench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "repro.moe.route", "repro.moe.experts")
