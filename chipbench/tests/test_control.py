"""The precision control: the reference answering in bfloat16 in the
program's place fails the comparison that a sound run passes."""
import json

import pytest

from chipbench import control, harness
from chipbench.tests.conftest import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("workload", ["sift128.bulk", "gist960.bulk"])
@pytest.mark.parametrize("kind", ["bf16_matmul", "bf16_rows"])
def test_bf16_reference_is_not_correct(workload, kind):
    loaded = harness.load_cell(BENCH, workload)
    loaded["mix"] = dict(loaded["mix"], sample_requests=1)
    r = control.readings(loaded, 7, kind, scale=0.08)
    assert r["correct"] is False
    assert r["checks"]["dist_gap"]["value"] > r["checks"]["dist_gap"]["limit"]


def test_float32_reference_is_correct():
    """The float64 reference's own answers, rounded to float32, pass."""
    import numpy as np

    from chipbench import check
    from chipbench.data import exact_top_k, make_base_and_queries

    base, queries = make_base_and_queries(2000, 64, 128, 64, 0.25, 0.5, 7)
    ids, d, _ = exact_top_k(queries, base, 10)
    ok, table = check.judge(check.compare(queries, base, ids, d.astype(np.float32), 10, 0),
                            harness.load_cell(BENCH, "sift128.bulk")["config"]["limits"])
    assert ok, table


def test_program_readings_judge_the_sound_search_and_its_shortened_twins(tiny_index):
    from chipbench.tests.conftest import tiny_loaded

    out = control.program_readings(tiny_loaded(), [5], index=tiny_index)
    kinds = {r["kind"]: r for r in out}
    assert set(kinds) == {"program", "short_pool", "short_walk"}
    assert kinds["program"]["correct"] is True, kinds["program"]["checks"]
    assert kinds["short_pool"]["change"] == {"efs": 24}
    assert (kinds["short_pool"]["checks"]["recall_miss"]["value"]
            > kinds["program"]["checks"]["recall_miss"]["value"])
