"""D4M 2.0 schema (paper ref [11]): edge table + transpose + degree table.

The degree table is maintained *at ingest time* by the combiner analogue
(`kvstore.degree_update`), exactly like attaching a summing iterator to
TedgeDeg in Accumulo. Queries use it for planning: find vertices of a given
degree (the paper's Fig. 4 query-selection procedure) and size query buffers.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.assoc import Assoc
from ..obs import span as obs_span
from .connector import DBserver, TablePair, delete as _delete
from .kvstore import degree_update


class DegreeTable:
    """Dense out/in-degree accumulator over the server's vertex-id space."""

    def __init__(self, server: DBserver, name: str):
        self.server = server
        self.name = name
        cap = server.id_capacity
        self.out_deg = jnp.zeros((cap,), jnp.float32)
        self.in_deg = jnp.zeros((cap,), jnp.float32)
        server.tables[name] = self

    def update(self, rid: np.ndarray, cid: np.ndarray) -> None:
        with obs_span("degree.update", n=len(rid)):
            ones_r = jnp.ones((len(rid),), jnp.float32)
            self.out_deg = degree_update(self.out_deg, jnp.asarray(rid),
                                         ones_r, use_pallas=False)
            self.in_deg = degree_update(self.in_deg, jnp.asarray(cid),
                                        jnp.ones((len(cid),), jnp.float32),
                                        use_pallas=False)

    def degrees(self, vertices) -> Assoc:
        with obs_span("schema.degrees", table=self.name):
            with obs_span("connector.plan"):
                ids = self.server.resolve_selector_plan(vertices).filter_ids()
                if ids is None:
                    ids = np.arange(len(self.server.keydict), dtype=np.int32)
            with obs_span("degree.read", n=len(ids)):
                out = np.asarray(self.out_deg)[ids]
                ind = np.asarray(self.in_deg)[ids]
            with obs_span("dict.decode", n=len(ids)):
                keys = self.server.keydict.decode(ids)
            with obs_span("assoc.build", n=len(ids)):
                rows = np.concatenate([keys, keys])
                cols = np.asarray(["OutDeg"] * len(ids) + ["InDeg"] * len(ids),
                                  object)
                vals = np.concatenate([out, ind])
                return Assoc(rows, cols, vals)

    def vertices_with_degree(self, target: float, kind: str = "out",
                             tol: float = 10 ** 0.5) -> np.ndarray:
        """Vertex names whose degree is within a factor ``tol`` of target
        (the paper buckets query vertices by degree decade)."""
        deg = np.asarray(self.out_deg if kind == "out" else self.in_deg)
        n = len(self.server.keydict)
        deg = deg[:n]
        hit = np.flatnonzero((deg >= target / tol) & (deg < target * tol))
        return self.server.keydict.decode(hit.astype(np.int32))


class EdgeSchema:
    """The full D4M 2.0 bundle: Tedge / TedgeT / TedgeDeg with auto-upkeep."""

    def __init__(self, server: DBserver, base: str):
        self.server = server
        self.pair = server[f"{base}_Tedge", f"{base}_TedgeT"]
        self.deg = DegreeTable(server, f"{base}_TedgeDeg")

    def put(self, a: Assoc) -> None:
        self.put_triple(*a.triples())

    def put_triple(self, rows, cols, vals) -> None:
        # ingest root: the pair write and the degree upkeep share one trace
        with obs_span("schema.put", table=self.pair.name, n=len(rows)):
            self.pair.put_triple(rows, cols, vals)
            with obs_span("degree.lookup", n=len(rows)):
                rid = self.server.keydict.lookup(np.asarray(rows, object))
                cid = self.server.keydict.lookup(np.asarray(cols, object))
            self.deg.update(rid, cid)

    def __getitem__(self, key) -> Assoc:
        return self.pair[key]

    def nnz(self) -> int:
        return self.pair.nnz()

    def delete(self) -> None:
        _delete(self.pair)
        self.server.drop(self.deg.name)
