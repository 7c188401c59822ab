"""Shared pieces of the benchmark's own tests: a cell small enough for the
CPU, with the same keys as the real ones."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))


def tiny_loaded(mix=None):
    """A loaded cell at CPU size: 600 x 32, HNSW m=8, efs 48, the jnp engine."""
    import json

    config = json.loads((ROOT / "chipbench" / "configs" / "sift128.json").read_text())
    config.update(n_base=600, n_query=64, dim=32)
    config["index"] = dict(config["index"], m=8, efc=48)
    config["search"] = dict(config["search"], engine="jnp", efs=48)
    mix = mix or {"loop": "closed", "clients": 2, "rows_per_request": 8, "buckets": [8],
                  "sample_requests": 8, "why": "test"}
    return {"cell": {"name": "tiny.bulk", "config": "tiny", "traffic": "tiny", "chips": 1},
            "config": config, "mix": mix, "end_to_end": ["qps", "setup_s"],
            "per_layer": [], "units": {"qps": "queries/s", "setup_s": "s"}}


@pytest.fixture(scope="module")
def tiny_index():
    """The tiny cell's index, built once per module."""
    from chipbench.data import make_base_and_queries
    from repro.core.index import AnnIndex

    loaded = tiny_loaded()
    c, d = loaded["config"], loaded["config"]["data"]
    base, _ = make_base_and_queries(c["n_base"], c["n_query"], c["dim"], d["n_clusters"],
                                    d["cluster_std"], d["sub_spread"], d["seed"])
    ix = c["index"]
    return AnnIndex.build(base, graph=ix["graph"], m=ix["m"], efc=ix["efc"],
                          profile_percentile=ix["profile_percentile"], seed=ix["build_seed"])
