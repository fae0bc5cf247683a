"""``correct`` at a size the CPU holds: the program passes the comparison
with the reference; the fp8 control and each fault a served cell can
have fail it. These drive the whole of a run (weights, engine, warm-up,
open loop, reference) except the look for a chip."""

import jax
import pytest

import _bench_tiny as tb
from bench import run


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tb.make_root(tmp_path_factory.mktemp("tiny"))


def test_program_is_correct(root):
    res, lines = run.execute(root, "tiny.chat", 2**31 + 7, 2.0, False)
    assert res["correct"], lines
    assert set(res["metrics"]) == {"setup_s", "ttft_p90_ms", "tpot_p90_ms",
                                   "out_tok_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["compiles_in_window"]["limit"] == 0


@pytest.mark.parametrize("cell", ["tiny.doc", "narrow.doc"])
def test_backlog_cell_is_correct_and_keeps_a_backlog(root, cell):
    res, lines = run.execute(root, cell, 5, 2.0, False)
    assert res["correct"], lines
    assert res["checks"]["compiles_in_window"]["value"] == 0
    assert set(res["metrics"]) == {"setup_s", "out_tok_s"}
    assert res["checks"]["backlog_left"]["value"] >= 1


def test_fp8_control_is_not_correct(root):
    """The reference in float8 e4m3 at each position of the same served
    requests, judged by the checks that decide ``correct``: its first
    choices lie further below the float32 best than the limit allows
    (readings in ``_bench_tiny.CONFIG``)."""
    res, lines = run.execute(root, "tiny.chat", 1, 2.0, False, control=True)
    assert res["correct"], lines
    ctl = res["control"]
    assert ctl["correct"] is False, lines
    assert ctl["checks"]["served_logit_gap"]["value"] > \
        ctl["checks"]["served_logit_gap"]["limit"]
    assert any(x.startswith("control check served_logit_gap") and
               x.endswith("FAIL") for x in lines)
    assert list(res)[-1] == "checks"


def _altered_token(engine):
    record = engine._record
    engine._record = lambda i, req, tok: record(i, req, (tok + 1) % 256)


def _stale_state(engine):
    step = engine._step
    engine._step = jax.jit(
        lambda p, t, caches, r, s: (step(p, t, caches, r, s)[0], caches))


@pytest.mark.parametrize("fault", [_altered_token, _stale_state],
                         ids=["token_altered", "state_unchanged"])
def test_fault_is_not_correct(root, fault):
    res, lines = run.execute(root, "tiny.chat", 4, 2.0, False, mutate=fault)
    assert not res["correct"], lines
    assert res["checks"]["served_logit_gap"]["value"] > \
        res["checks"]["served_logit_gap"]["limit"]
