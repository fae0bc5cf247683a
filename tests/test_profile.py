"""Reading the engine's spans back from a profiler trace
(``serve/profile.py``): the per-tick host time, the admission waits and
the device's idle gaps named by engine phase, on synthetic spans, and
``launch/serve.py --profile-dir`` end to end on the CPU."""

import pytest

from repro.launch import serve as serve_launch
from repro.serve import profile


def _span(a, b, name, **meta):
    return (a, b, "serve." + name, meta)


TICK = [
    _span(0.0, 10.0, "tick", tick=1),
    _span(0.5, 1.0, "admit"),
    _span(1.0, 4.0, "prefill"),
    _span(2.0, 3.0, "prefill_fetch", rid=7),
    _span(4.0, 8.0, "decode"),
    _span(4.0, 5.0, "decode.dispatch"),
    _span(5.0, 7.5, "decode.fetch"),
    _span(8.0, 9.5, "record", n_finished=1),
]


def test_tick_host_subtracts_only_fetch_time():
    # 10 s of tick less the prefill fetch (1 s) and the decode fetch
    # (2.5 s); the dispatch and the record stay host work.
    assert profile.tick_host_s(TICK) == [pytest.approx(6.5)]
    # A fetch outside the tick takes nothing off it.
    later = TICK + [_span(11.0, 12.0, "decode.fetch")]
    assert profile.tick_host_s(later) == [pytest.approx(6.5)]


def test_idle_gaps_are_named_by_the_innermost_engine_span():
    busy = [(1.5, 2.4), (3.0, 5.5), (6.0, 10.4)]
    spans = TICK + [_span(11.5, 13.0, "tick", tick=2)]
    gaps = {round(d, 6): n for n, d in
            profile.idle_gaps(busy, spans, 0.0, 12.0)}
    assert gaps == {1.5: "serve.admit", 0.6: "serve.prefill_fetch",
                    0.5: "serve.decode.fetch", 1.6: "none"}
    rep = profile.summary({"spans": spans, "busy": busy}, 0.0, 12.0)
    in_engine = sum(v for k, v in rep["idle_s"].items() if k != "none")
    assert in_engine == pytest.approx(2.6)
    assert sum(rep["idle_s"].values()) == pytest.approx(12.0 - 7.8)
    assert rep["longest_idle"][0] == ("none", pytest.approx(1.6))


def test_queue_wait_is_read_from_first_admissions_only():
    spans = [_span(0, 1, "admit.request", rid=1, slot=0, queue_ms=12.5),
             _span(2, 3, "admit.request", rid=2, slot=1, queue_ms=3.0),
             _span(4, 5, "admit.request", rid=1, slot=1, readmit=1)]
    assert profile.queue_ms(spans) == {1: 12.5, 2: 3.0}


def test_summary_window_defaults_to_the_ticks():
    spans = TICK + [_span(12.0, 14.0, "tick", tick=2),
                    _span(12.5, 13.0, "admit.request", rid=3,
                          queue_ms=40.0)]
    rep = profile.summary({"spans": spans, "busy": []})
    assert rep["window_s"] == 14.0 and rep["ticks"] == 2
    assert rep["tick_host_ms"] == pytest.approx(1e3 * (6.5 + 2.0) / 2)
    assert rep["queue_ms"] == {3: 40.0}
    assert "idle_s" not in rep          # no device plane
    # A window around the second tick alone.
    assert profile.summary({"spans": spans, "busy": []}, 11.0, 15.0
                           )["ticks"] == 1
    with pytest.raises(ValueError):
        profile.summary({"spans": [], "busy": []})


def test_launch_profile_dir_writes_the_engine_spans(tmp_path, capsys):
    run_dir = str(tmp_path / "prof")
    engine = serve_launch.main([
        "--arch", "qwen3-4b", "--smoke", "--paged", "--page-size", "8",
        "--chunk-size", "8", "--max-len", "64", "--batch", "2",
        "--requests", "3", "--max-new", "4", "--profile-dir", run_dir])
    assert len(engine.finished) == 3
    spans = profile.load(run_dir)["spans"]
    ticks = [s for s in spans if s[2] == "serve.tick"]
    assert len(ticks) == engine.ticks
    assert "profile: " + run_dir in capsys.readouterr().out
    with pytest.raises(SystemExit):
        serve_launch.main(["--arch", "qwen3-4b", "--smoke", "--paged",
                           "--no-telemetry", "--profile-dir", run_dir])
