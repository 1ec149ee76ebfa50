"""The Xing4.0 cell's planted faults (``benchmarks/families/xing4.py:_planted``)
at the tiny size of ``tests/test_xing4.py``: each fails the float32 tolerance
against the plain reference, and none sticks.  A file of its own: the suite
is spread over workers by file."""

import os
import sys

import pytest

import test_xing4 as base

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def sound(tokens):
    """(config, weights, the reference's side, computed once)."""
    cfg = base.tiny(held_experts=(2, 4))
    params = base.seeded(cfg)
    return cfg, params, base.reference_side(cfg, params, tokens)


tokens = base.tokens


@pytest.mark.parametrize("fault", [
    "no_column_step", "sinkhorn_bf16", "mtp_shift_one", "no_yarn_scale", "no_mtp_term"])
def test_a_planted_fault_is_refused(tokens, sound, fault):
    """Sinkhorn without its column step (20 converged rounds cannot be told
    from 19, so that is not the fault), Sinkhorn in bfloat16, the module's
    targets one ahead, YaRN's scale left out, the module's term dropped."""
    from benchmarks.families import xing4 as family

    cfg, params, want = sound
    with family._planted(fault):
        with pytest.raises(AssertionError):
            base.assert_matches_reference(cfg, params, tokens, want=want)


def test_nothing_of_a_planted_fault_sticks(tokens, sound):
    """Behind the faults above (the routines are jitted by name and JAX keeps
    their traces): the system as it stands is the reference again."""
    cfg, params, want = sound
    base.assert_matches_reference(cfg, params, tokens, want=want)
