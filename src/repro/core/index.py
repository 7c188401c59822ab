"""High-level ANNS index API: build -> profile angles -> search.

This is the user-facing entry point of the CRouting system:

    from repro.core.index import AnnIndex
    from repro.core.spec import SearchSpec

    idx = AnnIndex.build(base, graph="hnsw", metric="l2")
    ids, dists, stats = idx.search(
        queries, spec=SearchSpec(k=10, efs=100, router="crouting"))
    print(stats.dist_calls.mean())          # typed SearchStats, not a dict

``SearchSpec`` is the single request object (router registry name, beam
width, engine, estimate strategy, ...); ``stats`` is a typed
``SearchStats``.  ``search`` accepts a ``SearchSpec`` or ``None`` only —
kwarg-style configuration (``idx.search(q, k=10, router="crouting")``)
raises ``TypeError``.

Index persistence is a plain .npz (content-addressed in benchmarks' cache)
stamped with ``format_version``; ``load`` refuses files newer than it knows
how to read.  A replacement serving node re-pulls only its shard
(DESIGN.md §6).

Crash safety (DESIGN.md §10): ``save`` writes a temp file, fsyncs, stamps a
content checksum, and atomically renames into place — a ``kill -9`` at any
instant leaves either the old version or the new one at ``path``, never a
torn file.  ``load`` verifies the checksum and raises a typed
``CorruptIndexError`` on truncation/corruption instead of surfacing an
opaque ``zipfile``/``zlib`` error.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import numpy as np

from repro.durable.atomic import (atomic_write_npz, read_npz,
                                  verify_checksum)
from repro.fault import CorruptIndexError

from repro.core import distances as D
from repro.core.angles import AngleProfile, sample_angle_profile
from repro.core.graph import GraphIndex
from repro.core.hnsw import build_hnsw
from repro.core.nsg import build_nsg
from repro.core.knn_graph import build_knn_graph
from repro.core.search import SearchResult, build_search_fn
from repro.core.spans import span
from repro.core.spec import SearchSpec, SearchStats, resolve_search_spec

GRAPH_BUILDERS = {"hnsw": build_hnsw, "nsg": build_nsg, "knn": build_knn_graph}

# What a bare `idx.search(queries)` means (matches the historical kwarg
# defaults; note SearchSpec() itself defaults to router="none").
DEFAULT_SEARCH = SearchSpec(k=10, efs=100, router="crouting")

# .npz payload schema version.  v1 (implicit — no stamp): pre-PR4 files
# missing theta_nq/theta_secs.  v2: format_version + theta_corpus_n stamps.
# v3: content ``checksum`` entry, required and verified on load.
FORMAT_VERSION = 3


@dataclasses.dataclass
class AnnIndex:
    graph: GraphIndex
    profile: Optional[AngleProfile] = None

    # --- construction --------------------------------------------------------
    @classmethod
    def build(cls, base: np.ndarray, graph: str = "hnsw", metric: str = "l2",
              profile_percentile: float = 90.0, seed: int = 0,
              profile: bool = True, **graph_kw) -> "AnnIndex":
        g = GRAPH_BUILDERS[graph](base, metric=metric, seed=seed, **graph_kw) \
            if graph != "knn" else build_knn_graph(base, metric=metric, **graph_kw)
        prof = sample_angle_profile(g, percentile=profile_percentile, seed=seed) \
            if profile else None
        return cls(graph=g, profile=prof)

    # --- search ---------------------------------------------------------------
    def _engine(self, cfg: SearchSpec):
        # build_search_fn memoizes per (graph identity, canonical spec)
        return build_search_fn(self.graph, cfg)

    def search(self, queries: np.ndarray, spec: Optional[SearchSpec] = None
               ) -> Tuple[np.ndarray, np.ndarray, SearchStats]:
        """Batched search.  Returns (ids [B,k], dists [B,k], SearchStats).

        ``spec`` is the one configuration object; its ``metric`` and
        ``use_hierarchy`` fields are overridden from the index's graph, and
        ``cos_theta=None`` resolves to the sampled angle profile.  A pruning
        router with neither a profile nor an explicit ``cos_theta`` raises
        ``ValueError`` — the old silent ``0.0`` fallback made such routers
        prune at theta*=90 degrees and quietly tanked recall; non-pruning
        routers (which never read the threshold) keep the ``0.0``
        placeholder.  Slots with no result carry id -1 and distance +inf.
        Anything other than a ``SearchSpec`` (or ``None``) raises
        ``TypeError``.  Its phases are host spans (``core/spans.py``):
        ``index.prepare``, ``index.launch`` and ``index.fetch``, the copy
        of the results to the host.
        """
        import jax.numpy as jnp

        from repro.core.routers import get_router

        with span("index.prepare"):
            spec = resolve_search_spec(spec, DEFAULT_SEARCH, "AnnIndex.search")
            queries = D.preprocess_vectors(
                np.ascontiguousarray(queries, np.float32), self.graph.metric)
            cos_theta = spec.cos_theta
            if cos_theta is None:
                if self.profile is not None:
                    cos_theta = self.profile.cos_theta_star
                elif get_router(spec.router).prunes:
                    raise ValueError(
                        f"router {spec.router!r} prunes on the angle threshold, "
                        "but this index was built with profile=False and the "
                        "spec carries no explicit cos_theta — the old fallback "
                        "of cos_theta=0.0 silently pruned at theta*=90deg. "
                        "Build with profile=True, or set SearchSpec.cos_theta.")
                else:
                    cos_theta = 0.0   # never read by a non-pruning router
            k = spec.k
            cfg = dataclasses.replace(
                spec, efs=max(spec.efs, k), metric=self.graph.metric,
                use_hierarchy=self.graph.upper_neighbors is not None)
            _, fn = self._engine(cfg)
            q_dev = jnp.asarray(queries)
            ct_dev = jnp.asarray(cos_theta, jnp.float32)
        with span("index.launch"):
            res: SearchResult = fn(q_dev, ct_dev)
        with span("index.fetch"):
            ids = np.asarray(res.ids[:, :k]).astype(np.int64)
            dists = np.array(res.dists[:, :k])
            # empty slots resolve to the pad row: mask BOTH columns (an id of
            # -1 must never ship with the pad row's finite distance)
            pad = ids >= self.graph.n
            ids[pad] = -1
            dists[pad] = np.inf
            stats = SearchStats.from_result(res, router=spec.router)
        return ids, dists, stats

    # --- persistence ----------------------------------------------------------
    def _payload(self) -> Dict[str, np.ndarray]:
        """The v3 .npz payload (sans checksum — the atomic writer stamps
        it).  Shared by ``save`` and the durability checkpoints, which
        embed this payload and extend it with mutation state."""
        g = self.graph
        payload = dict(
            format_version=np.asarray(FORMAT_VERSION),
            vectors=g.vectors, neighbors=g.neighbors, edge_eu_dist=g.edge_eu_dist,
            entry_point=np.asarray(g.entry_point), metric=np.asarray(g.metric),
            kind=np.asarray(g.kind),
        )
        if g.norms is not None:
            payload["norms"] = g.norms
        if g.upper_neighbors:
            payload["n_upper"] = np.asarray(len(g.upper_neighbors))
            for i, (ids, mat) in enumerate(zip(g.upper_ids, g.upper_neighbors)):
                payload[f"upper_ids_{i}"] = ids
                payload[f"upper_nbrs_{i}"] = mat
        if self.profile is not None:
            payload["theta_samples"] = self.profile.samples
            payload["theta_star"] = np.asarray(self.profile.theta_star)
            payload["theta_pct"] = np.asarray(self.profile.percentile)
            payload["theta_nq"] = np.asarray(self.profile.n_sample_queries)
            payload["theta_secs"] = np.asarray(self.profile.sample_secs)
            payload["theta_corpus_n"] = np.asarray(self.profile.corpus_n)
        return payload

    def save(self, path: str):
        """Atomically persist the index (temp file + fsync + rename).

        The payload carries a content checksum; a crash at ANY point leaves
        ``path`` holding either the previous version or the complete new
        one — ``load`` can never silently accept a torn write.  Failpoint
        sites: ``index.save.write`` (raise = crash mid-save; ``corrupt`` /
        ``truncate`` = damage the bytes before publication, exercising the
        ``load`` integrity checks) and ``index.save.rename`` (crash in the
        write→publish window).  The recipe lives in ``repro.durable.atomic``
        and is shared with checkpoints and manifests (DESIGN.md §11).
        """
        atomic_write_npz(path, self._payload(),
                         write_site="index.save.write",
                         rename_site="index.save.rename")

    @classmethod
    def load(cls, path: str) -> "AnnIndex":
        """Load a persisted index, verifying integrity first.

        Truncated or corrupted files — unreadable zip structure, entry
        decompression failures, or (v3+) a content-checksum mismatch —
        raise ``CorruptIndexError``.  A future ``format_version`` raises
        ``ValueError`` (an incompatibility, not damage).
        """
        z = read_npz(path)
        cls._check_version(z, path)
        return cls._from_payload(z)

    @staticmethod
    def _check_version(z: Dict[str, np.ndarray], path: str) -> int:
        """Version + checksum gate shared with the checkpoint reader.

        v1 files predate the stamp; anything NEWER than we know must fail
        loudly instead of silently defaulting fields it doesn't understand.
        v3+ files always carry a checksum (verified here); a missing or
        stale one means the payload was modified after the save stamped it.
        """
        version = int(z["format_version"]) if "format_version" in z else 1
        if version > FORMAT_VERSION:
            raise ValueError(
                f"{path}: index format_version={version} is newer than this "
                f"build understands (max {FORMAT_VERSION}); upgrade the code "
                "or re-save the index with a compatible version")
        if version >= 3:
            verify_checksum(path, z, required=True)
        return version

    @classmethod
    def _from_payload(cls, z: Dict[str, np.ndarray]) -> "AnnIndex":
        """Rebuild graph + profile from a (verified) payload dict.  Extra
        keys (a checkpoint's mutation state) are ignored."""
        version = int(z["format_version"]) if "format_version" in z else 1
        upper_ids = upper_nbrs = None
        if "n_upper" in z:
            k = int(z["n_upper"])
            upper_ids = [z[f"upper_ids_{i}"] for i in range(k)]
            upper_nbrs = [z[f"upper_nbrs_{i}"] for i in range(k)]
        g = GraphIndex(
            vectors=z["vectors"], neighbors=z["neighbors"],
            edge_eu_dist=z["edge_eu_dist"], entry_point=int(z["entry_point"]),
            metric=str(z["metric"]), norms=z.get("norms"),
            upper_ids=upper_ids, upper_neighbors=upper_nbrs, kind=str(z["kind"]))
        prof = None
        if "theta_samples" in z:
            th = float(z["theta_star"])
            if version >= 2:
                # v2 files always carry these; read strictly (a missing key
                # here means corruption, not an old writer)
                nq, secs = int(z["theta_nq"]), float(z["theta_secs"])
                corpus_n = int(z["theta_corpus_n"])
            else:
                # v1 (pre-PR4) files legitimately lack them
                nq = int(z["theta_nq"]) if "theta_nq" in z else 0
                secs = float(z["theta_secs"]) if "theta_secs" in z else 0.0
                corpus_n = 0
            prof = AngleProfile(theta_star=th, cos_theta_star=float(np.cos(th)),
                                percentile=float(z["theta_pct"]),
                                samples=z["theta_samples"],
                                n_sample_queries=nq, sample_secs=secs,
                                corpus_n=corpus_n)
        return cls(graph=g, profile=prof)
