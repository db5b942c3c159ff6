"""Device time per training step of the latent attention layers (scope
``repro.mla``: projections, attention, their backward), in ms."""

from chipbench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "repro.mla")
