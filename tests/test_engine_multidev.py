"""Multi-device integration tests for the partitioned-comm engine.

Each test runs in a subprocess with 8 fake host devices so the main pytest
process keeps exactly one device (dry-run isolation requirement).
"""

import pytest


@pytest.mark.slow
def test_ring_collectives(multidev):
    out = multidev("check_collectives.py")
    assert "ALL-OK" in out


@pytest.mark.slow
def test_earlybird_grad_sync(multidev):
    out = multidev("check_earlybird.py")
    assert "ALL-OK" in out
    assert "grad equivalence ok" in out
    assert "HLO placement ok" in out


@pytest.mark.slow
def test_flash_decode(multidev):
    out = multidev("check_flash_decode.py")
    assert "ALL-OK" in out


@pytest.mark.slow
def test_launch_steps_mini_dryrun(multidev):
    """Train/prefill/decode lower+compile on an 8-device (2x4) mesh across
    dense / MoE / SSM families — the production dry-run path, in pytest."""
    out = multidev("check_launch_steps.py", timeout=900)
    assert "ALL-OK" in out
    assert out.count("decode ok") >= 5
