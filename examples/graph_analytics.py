"""Full graph-analytics run: all five Ligra apps on a reordered dataset,
including the Pallas degree-binned SpMV (kernel K1) as the PageRank edge-map,
a backend-selection section (FlatBackend vs the fused-Pallas EllBackend from
kernels.edge_map), a packed-storage section (repro.pack: hot/cold segmented
compressed CSR with analytics running directly over it), plus a streaming
section: DeltaGraph ingest with incremental PageRank refresh and online DBG
maintenance (repro.stream), a batched-serving section: K concurrent
queries answered in one fused pass per iteration against refcounted graph
snapshots while ingest churns underneath (repro.serve), and a health-plane
section: SLO burn rates plus a deliberately induced latency breach whose
flight-recorder dump carries the offending query's causal flow chain
(repro.obs.slo / repro.obs.flight).

  PYTHONPATH=src python examples/graph_analytics.py [dataset]
"""
import sys

sys.path.insert(0, "src")

import time

import jax.numpy as jnp
import numpy as np

from repro.apps import bc, pagerank, pagerank_delta, radii, sssp, to_arrays
from repro.cachesim import scaled_hierarchy
from repro.core.reorder import dbg_spec, reorder_graph
from repro.graph import datasets
from repro.kernels.csr_spmv.ops import dbg_spmv, ell_pack_groups
from repro.kernels.csr_spmv.ref import csr_spmv_ref
from repro.kernels.pack_spmv.ops import pack_spmv
from repro.pack import flat_csr_nbytes, pack_graph, packed_backend
from repro.stream import StreamService, layout_mpka, packed_mpka


def main():
    name = sys.argv[1] if len(sys.argv) > 1 else "lj"
    g = datasets.load(name, scale="small")
    gw = datasets.load_weighted(name, scale="small")
    print(f"dataset {name}: V={g.num_vertices:,} E={g.num_edges:,}")

    g2, res = reorder_graph(g, "dbg", degree_source="out")
    print(f"DBG reordering: {res.seconds:.3f}s, {res.num_groups} groups")
    ga = to_arrays(g2)
    gw2 = reorder_graph(gw, "dbg", degree_source="in")[0]
    gaw = to_arrays(gw2)

    for label, fn, args in [
        ("PR", pagerank, (ga,)),
        ("PRD", pagerank_delta, (ga,)),
        ("SSSP", sssp, (gaw, jnp.int32(0))),
        ("BC", bc, (ga, jnp.int32(0))),
        ("Radii", radii, (ga, jnp.int32(0))),
    ]:
        t0 = time.time()
        out = fn(*args)
        first = out[0].block_until_ready()
        iters = int(out[-1])  # PR/PRD/SSSP/Radii: iterations; BC: BFS levels
        print(f"  {label:6s} iters={iters}  {time.time()-t0:.2f}s  "
              f"finite={bool(jnp.isfinite(jnp.asarray(first, jnp.float32)).all())}")

    # ----- backend selection: the same apps over the fused edge-map backend --
    # to_arrays(g) is the flat oracle; to_arrays(g, backend="ell") routes every
    # edge_map_pull/push through the fused Pallas kernels (kernels.edge_map):
    # gather + weight-add + frontier-mask + reduce in ONE pass over ELL
    # tiles, push included (a push with a reduction is the transposed pull).
    print("\nedge-map backends (repro.apps.engine):")
    ga_ell = to_arrays(g2, backend="ell")
    gaw_ell = to_arrays(gw2, backend="ell")
    from repro.kernels.edge_map.ops import fused_edge_map_bytes
    slots = sum(int(np.prod(t.idx.shape)) for t in ga_ell.in_tiles)
    print(f"  EllBackend: {len(ga_ell.in_tiles)} ELL width classes, "
          f"{slots/g2.num_edges:.2f} slots/edge, fused pull "
          f"{fused_edge_map_bytes(ga_ell.in_tiles, g2.num_vertices)/1e6:.1f} "
          f"MB/iter (single pass)")
    r_flat2, _ = pagerank(ga)
    r_ell, _ = pagerank(ga_ell)
    d_flat2, _ = sssp(gaw, jnp.int32(0))
    d_ell, _ = sssp(gaw_ell, jnp.int32(0))
    print(f"  PageRank flat vs fused: max err "
          f"{float(jnp.abs(r_flat2 - r_ell).max()):.1e}; SSSP bit-identical: "
          f"{bool(np.array_equal(np.asarray(d_flat2), np.asarray(d_ell)))} "
          f"(direction-optimizing pull/push switch on frontier density)")

    # Pallas kernel as the PageRank edge map (pull-mode SpMV over DBG groups)
    spec = dbg_spec(max(1.0, g2.in_degrees().mean()))
    groups = ell_pack_groups(g2, spec.boundaries, row_tile=64, width_tile=128)
    x = jnp.asarray(np.random.default_rng(0).random(g2.num_vertices, np.float32))
    y_kernel = dbg_spmv(x, groups, g2.num_vertices, row_tile=64, width_tile=128)
    y_ref = csr_spmv_ref(x, ga.in_src, ga.in_dst, ga.in_w, g2.num_vertices)
    err = float(jnp.abs(y_kernel - y_ref).max())
    print(f"  Pallas degree-binned SpMV vs CSR oracle: max err {err:.2e}")
    widths = [gr.idx.shape[1] for gr in groups]
    occ = [gr.w.sum() / gr.idx.size for gr in groups]
    print(f"  ELL group widths {widths} lane-occupancy "
          f"{[f'{o:.2f}' for o in occ]} (geometric bins bound padding)")

    # ----- packed storage: hot/cold segmented compressed CSR (repro.pack) ---
    print("\npacked storage (repro.pack):")
    pg = pack_graph(g2)
    flat_be = flat_csr_nbytes(g2) / (2 * g2.num_edges)
    print(f"  bytes/edge: flat CSR {flat_be:.2f} -> packed "
          f"{pg.bytes_per_edge():.2f} (hot packing factor "
          f"{pg.in_adj.packing_factor:.2f}, "
          f"{pg.in_adj.hot_edges / pg.num_edges:.0%} of edges in the "
          f"fixed-stride hot segment, pack {pg.pack_seconds:.3f}s)")
    pb = packed_backend(pg)  # the apps.engine backend over packed storage
    r_flat, _ = pagerank(to_arrays(pg.unpack()))
    r_pack, it = pagerank(pb)
    dev = float(np.abs(np.asarray(r_flat) - np.asarray(r_pack)).max())
    print(f"  PageRank via apps.pagerank over PackedBackend: {int(it)} iters,"
          f" max dev vs flat CSR {dev:.1e} (min/max apps bitwise)")
    y_pack = pack_spmv(x, pg.in_adj)
    print(f"  pack_spmv (Pallas hot segment + decoded cold tiles) vs CSR "
          f"oracle: max err {float(jnp.abs(y_pack - y_ref).max()):.2e}")
    levels = scaled_hierarchy(g2.num_vertices)
    m_flat = layout_mpka(g2, None, levels, include_structure=True)
    m_pack = packed_mpka(pg, levels, pin_hot=True)
    print(f"  storage-aware L3 MPKA: flat DBG {m_flat['l3_mpka']:.1f} -> "
          f"DBG+pack {m_pack['l3_mpka']:.1f} "
          f"(GRASP-lite pinned {m_pack['l3_pinned_mpka']:.1f})")

    # ----- streaming: ingest edge batches, refresh PageRank incrementally ----
    print("\nstreaming ingest (repro.stream):")
    svc = StreamService(g)
    svc.pagerank()  # initial full solve
    rng = np.random.default_rng(1)
    v = g.num_vertices
    for b in range(3):
        k = max(64, g.num_edges // 200)
        es, ed, _ = svc.dg.alive_edges()
        drop = rng.choice(es.shape[0], size=k // 4, replace=False)
        st = svc.ingest(
            add_src=rng.integers(0, v, k), add_dst=rng.integers(0, v, k),
            del_src=es[drop], del_dst=ed[drop])
        t0 = time.time()
        ranks = svc.pagerank()
        dt = time.time() - t0
        full, it_full = pagerank(to_arrays(svc.snapshot()), tol=1e-10,
                                 max_iters=256)
        err = float(np.abs(ranks - np.asarray(full)).max())
        print(f"  batch {b}: +{st.inserted}/-{st.deleted} edges, "
              f"refresh {svc.pr.last_iters} push iters in {dt:.3f}s "
              f"(full recompute {int(it_full)} iters), max err {err:.1e}, "
              f"regrouped {st.moved_vertices} vertices in "
              f"{st.regroup_seconds*1e3:.2f} ms")
    loc = svc.locality()
    print(f"  locality after churn: L3 MPKA identity "
          f"{loc['identity']['l3_mpka']:.1f} vs live-DBG "
          f"{loc['incremental_dbg']['l3_mpka']:.1f}")

    # ----- batched serving: K queries, one fused pass per iteration ---------
    # K concurrent PageRank/SSSP queries become a (V, K) property plane; the
    # admission queue coalesces them into width-K batches and every batch
    # pins an immutable snapshot, so the ingest below never corrupts an
    # answer (results are stamped with the version they were computed on).
    from repro.serve import GraphServeService, Query, ServeConfig

    print("\nbatched serving (repro.serve):")
    serve = GraphServeService(g, ServeConfig(max_width=4, publish_every=1))
    for root in rng.integers(0, v, 4):
        serve.submit(Query("sssp", root=int(root)))
    t0 = time.time()
    batch = serve.drain()  # ONE width-4 fused run answers all four
    print(f"  4 SSSP roots in one batch: {time.time()-t0:.2f}s, iters "
          f"{[r.iters for r in batch]}, snapshot v{batch[0].snapshot_version}")
    qid = serve.submit(Query("pagerank"))  # personalizable: Query(root=...)
    serve.submit(Query("pagerank", root=int(rng.integers(0, v))))
    k2 = max(64, g.num_edges // 200)
    serve.ingest(add_src=rng.integers(0, v, k2),
                 add_dst=rng.integers(0, v, k2))  # churn BEFORE dispatch
    for r in serve.drain():
        kind = "global PR" if r.qid == qid else "personalized PR"
        print(f"  {kind}: {r.iters} iters against snapshot "
              f"v{r.snapshot_version} (submitted at epoch {r.submit_epoch}, "
              f"latency {r.latency*1e3:.0f} ms)")
    print(f"  metrics: {serve.metrics.summary()}")

    # ----- observability: trace + edge-map counters around a served burst ---
    # repro.obs is off by default (instrumented call sites cost one is-check);
    # enable() starts recording nested spans from every layer and install()
    # hooks the engine dispatch, all without perturbing a single result bit.
    from repro.obs import counters as obs_counters
    from repro.obs import trace as obs_trace
    from repro.obs.metrics import MetricsRegistry

    print("\nobservability (repro.obs):")
    tracer = obs_trace.enable()
    ctrs = obs_counters.install(registry=MetricsRegistry())
    for root in rng.integers(0, v, 4):
        serve.submit(Query("sssp", root=int(root)))
    serve.ingest(add_src=rng.integers(0, v, 64),
                 add_dst=rng.integers(0, v, 64))
    serve.drain()
    obs_counters.uninstall()
    obs_trace.disable()
    path = tracer.save("/tmp/graph_analytics_trace.json")
    spans = {e["name"] for e in tracer.export()["traceEvents"]
             if e["ph"] == "X"}
    print(f"  {len(tracer.export()['traceEvents'])} trace events "
          f"({len(spans)} distinct spans) -> {path} (open in Perfetto)")
    iters_sum = {k: int(val) for k, val in ctrs.summary().items()
                 if k.startswith("edge_map.iters.")}
    print(f"  edge-map telemetry: {iters_sum} "
          f"(true loop iterations, reported by the batch dispatcher)")

    # ----- health plane: SLO burn rates + flight-recorder anomaly dumps -----
    # The flight recorder is the always-on production counterpart of the
    # tracer: a fixed-capacity ring of recent events that anomalies snapshot
    # automatically.  Here we arm it and induce a breach on purpose: an
    # impossibly tight latency SLO turns the first served batch into an SLO
    # breach, whose dump carries the offending query's id-linked
    # submit → wait → solve → result flow chain (select its qid in Perfetto).
    from repro.obs import flight as obs_flight

    print("\nhealth plane (repro.obs.slo + repro.obs.flight):")
    obs_flight.install(capacity=2048, dump_dir="/tmp/flight", cooldown_s=0.0)
    tight = GraphServeService(g, ServeConfig(
        max_width=2, slo_latency_p99_s=1e-9))  # any answer breaches
    for root in rng.integers(0, v, 2):
        tight.submit(Query("sssp", root=int(root)))
    tight.drain()
    h = tight.health()
    lat = h["objectives"]["serve.latency"]
    print(f"  health: {h['status']} — serve.latency worst burn rate "
          f"{lat['worst_burn']:.1f}x over "
          f"{'/'.join(lat['windows'])} windows")
    fr = obs_flight.get_flight()
    print(f"  anomalies: {[t['reason'] for t in fr.triggers]} -> dumps in "
          f"/tmp/flight ({len(fr)} ring events); healthy-plane check: "
          f"stream ingest {serve.stream.health()['status']}")
    obs_flight.uninstall()


if __name__ == "__main__":
    main()
