"""A whole run at CPU size, with the chip check skipped: the result line, the
refusal without a TPU, and planted faults in the timed path that the
comparison has to catch."""
import dataclasses
import io
import json
import time

import numpy as np
import pytest

from chipbench import harness
from chipbench.tests.conftest import ROOT, tiny_loaded

SEED = 3


def _serve_with(index, fault=None):
    """A ``serve`` for ``harness.measure``: the prebuilt index behind a fresh
    frontend, its engine session wrapped by ``fault``."""
    def serve(config, mix, base):
        from repro.serve import ServeFrontend

        fe = ServeFrontend(index, harness.search_spec(config), buckets=tuple(mix["buckets"]),
                           max_pending_rows=harness.PENDING_ROWS)
        if fault is not None:
            eng = fe._base.engine
            eng.search_padded = fault(eng, eng.search_padded)
        return index, fe, None
    return serve


def _run(index, fault=None, loaded=None):
    out, err = io.StringIO(), io.StringIO()
    result = harness.measure(loaded or tiny_loaded(), SEED, 1.0, False,
                             time.perf_counter(), require_tpu=False, out=out, err=err,
                             serve=_serve_with(index, fault))
    return result, err.getvalue()


def test_result_line_keys_and_a_sound_run_is_correct(tiny_index):
    result, err = _run(tiny_index)
    assert list(result) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"qps", "setup_s"}
    assert result["metrics"]["qps"]["value"] > 0
    assert set(result["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    assert set(result["checks"]) == {"recall_miss", "dist_gap", "bad_rows", "unanswered"}
    for name, v in result["checks"].items():
        assert f"check {name}: {v['value']!r} (limit {v['limit']!r})" in err
    json.dumps(result, allow_nan=False)


def _unchanged_state(eng, search_padded):
    """The hop loop returns its state unchanged: no node is expanded."""
    from repro.serve.backends import make_session

    stuck = make_session(eng.index, eng.spec.replace(max_hops=0))
    return stuck.search_padded


def _half_batch(eng, search_padded):
    """Half of each dispatch's rows left out: they get the first half's answers."""
    def go(q, n_valid, k, ct):
        ids, dists, stats = search_padded(q, n_valid, k, ct)
        half = max(1, n_valid // 2)
        ids, dists = ids.copy(), dists.copy()
        ids[half:n_valid] = ids[np.arange(half, n_valid) % half]
        dists[half:n_valid] = dists[np.arange(half, n_valid) % half]
        return ids, dists, stats
    return go


def _altered_answer(eng, search_padded):
    """One answer altered where it is produced: the last id of each row."""
    n = eng.index.graph.n

    def go(q, n_valid, k, ct):
        ids, dists, stats = search_padded(q, n_valid, k, ct)
        ids = ids.copy()
        ids[:, -1] = (ids[:, -1] + 1) % n
        return ids, dists, stats
    return go


def _short_walk(eng, search_padded):
    """The hop loop cut short: every query stops after 8 node expansions, a
    sixth of its walk at this size."""
    from repro.serve.backends import make_session

    return make_session(eng.index, eng.spec.replace(max_hops=8)).search_padded


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch, _altered_answer, _short_walk],
                         ids=["state_unchanged", "half_batch_left_out", "answer_altered",
                              "walk_cut_short"])
def test_planted_fault_is_not_correct(tiny_index, fault):
    result, _ = _run(tiny_index, fault)
    assert result["attempted"] > 0
    assert result["correct"] is False, result["checks"]


def test_index_is_built_once_per_checkout_and_then_loaded(tmp_path):
    c = tiny_loaded()["config"]
    base, _ = harness.cell_data(c)
    built, build_s = harness.cached_index(c, base, tmp_path)
    loaded, again = harness.cached_index(c, base, tmp_path)
    assert build_s > 0 and again is None
    np.testing.assert_array_equal(built.graph.neighbors, loaded.graph.neighbors)
    assert built.profile.cos_theta_star == loaded.profile.cos_theta_star
    other = dict(c, index=dict(c["index"], efc=32))
    assert harness.index_key(other, 600) != harness.index_key(c, 600)
    harness.cached_index(other, base, tmp_path)
    assert len(list(tmp_path.glob("sift128-*.npz"))) == 1


def test_measured_path_refuses_a_non_tpu_device():
    import jax

    with pytest.raises(harness.NoChip):
        harness.require_chips(jax.devices(), 1)

    @dataclasses.dataclass
    class FakeTpu:
        platform: str = "tpu"

    with pytest.raises(harness.NoChip):
        harness.require_chips([FakeTpu()], 4)
    harness.require_chips([FakeTpu()], 1)


def test_run_py_exits_2_without_a_tpu(capsys):
    import importlib.util

    spec = importlib.util.spec_from_file_location("chipbench_run", ROOT / "chipbench" / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    rc = run.main(["--workload", "sift128.bulk", "--seed", "1", "--seconds", "1"])
    assert rc == 2
    assert capsys.readouterr().out == ""
