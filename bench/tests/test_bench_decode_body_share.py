"""The reader of ``paged_attn.decode_body_share``: walked block-table
entries that the head-batched decode body ran, over all walked entries,
from the program's totals of the traced window (``program_obs``)."""

from types import SimpleNamespace

import pytest

from bench import harness, program_obs, serve, spec
from bench.serve import StepRecord

NAME = "paged_attn.decode_body_share"


def _window(obs, n=3):
    obs.steps.extend(StepRecord([(100 + i, 1)], 1, 1, 0, 0.5)
                     for i in range(n))
    return harness.Context(cfg=None, peaks={}, steps=obs.steps[1:],
                           trace=None, compiles=0, obs=obs)


@pytest.mark.parametrize("decode,walked", [(128, 128), (96, 128), (0, 64)])
def test_share_of_walked_entries_the_decode_body_ran(decode, walked):
    obs = serve.make_obs()
    ctx = _window(obs)
    obs.profiled.entries_walked = walked
    obs.profiled.entries_decode_body = decode
    assert program_obs.profiled(ctx) is obs.profiled
    assert spec.metric_reader(NAME)(ctx) == pytest.approx(
        100.0 * decode / walked)


def test_left_out_where_the_program_counts_no_body():
    obs = serve.make_obs()
    ctx = _window(obs)
    # no table walked in the traced window
    assert spec.metric_reader(NAME)(ctx) is None
    # a program whose totals have no body count
    obs.profiled = SimpleNamespace(spans={}, entries_live=32,
                                   entries_walked=64)
    assert spec.metric_reader(NAME)(ctx) is None
    # steps with no hook object
    assert spec.metric_reader(NAME)(harness.Context(
        cfg=None, peaks={}, steps=[StepRecord([(1, 1)], 1, 1, 0, 0.5)],
        trace=None, compiles=0)) is None
