"""Device-resident sharded refinement (repro.dist.refine_sharded).

Invariants: bit-parity of the shard_map sweep loop against the NumPy host
mirror on seeded meshes (integer weights ⇒ f32 sums are exact ⇒ identical
labels), cut monotone per sweep, balance corridor held on globally reduced
part weights, zero disconnected parts after the closing repair, sharded
cut within 1% of the host FM refiner, exactly one boundary-label
all_gather per sweep (trace counters), the guard fallback on a
rejected result, and propagation of device-path errors.  The
8-device behaviour runs in a subprocess via the ``multi_device_run``
conftest fixture (the main test process keeps 1 device).
"""

import numpy as np
import pytest

from repro import obs
from repro.core import balance_corridor, edge_cut, partition_metrics, refine_boundary
from repro.core.pipeline import PartitionPipeline, parse_refine
from repro.dist.refine_sharded import (
    build_frontier_plan,
    kway_sharded_stage,
    refine_sharded_host,
    refine_sharded_stage,
    run_sharded_sweeps,
)
from repro.mesh import box_mesh, build_csr


def _seeded_case(mesh, nparts, seed, frac=0.12):
    """RCB partition + a seeded perturbation: refinement has real work and
    the corridor (widened to the perturbed state) has slack."""
    ctx = PartitionPipeline(bisect="rcb", post=()).run(mesh, nparts)
    g = ctx.require_graph()
    rng = np.random.default_rng(seed)
    parts = ctx.parts.copy()
    sel = rng.random(g.n) < frac
    parts[sel] = rng.integers(0, nparts, sel.sum())
    corr = balance_corridor(parts, nparts, ctx.weights, 0.05)
    return g, parts, ctx.weights, corr


CASES = [(box_mesh(8, 8, 6), 8, 3), (box_mesh(6, 6, 4), 4, 5),
         (box_mesh(9, 8, 6), 12, 7)]


@pytest.mark.parametrize("mesh,nparts,seed", CASES)
def test_device_host_bit_parity(mesh, nparts, seed):
    """shard_map sweep loop ≡ NumPy mirror, label for label."""
    g, parts, w, corr = _seeded_case(mesh, nparts, seed)
    fp = build_frontier_plan(g, parts, nparts, weights=w)
    out_d, rec_d, info_d = run_sharded_sweeps(fp, parts, nparts, sweeps=10,
                                              corridor=corr)
    out_h, rec_h, info_h = refine_sharded_host(fp, parts, nparts, sweeps=10,
                                               corridor=corr)
    assert np.array_equal(out_d, out_h)
    assert info_d["moves"] == info_h["moves"]
    assert [r.moves for r in rec_d] == [r.moves for r in rec_h]
    # the internally tracked cut (Σ fresh gains) matches the real cut
    assert info_d["cut"] == pytest.approx(edge_cut(g, out_d))


@pytest.mark.parametrize("mesh,nparts,seed", CASES)
def test_sweeps_monotone_and_corridor(mesh, nparts, seed):
    g, parts, w, corr = _seeded_case(mesh, nparts, seed)
    fp = build_frontier_plan(g, parts, nparts, weights=w)
    out, records, info = run_sharded_sweeps(fp, parts, nparts, sweeps=10,
                                            corridor=corr)
    assert info["moves"] > 0          # the perturbation left real work
    for r in records:
        assert r.cut_after <= r.cut_before + 1e-6
    pw = np.bincount(out, weights=np.asarray(w, float), minlength=nparts)
    assert pw.min() >= corr[0] - 1e-9
    assert pw.max() <= corr[1] + 1e-9
    assert set(np.unique(out)) == set(range(nparts))


@pytest.mark.parametrize("mesh,nparts,seed", CASES)
def test_cut_within_one_percent_of_host_fm(mesh, nparts, seed):
    """The acceptance gate, in-process: sharded refined cut ≤ 1.01 × the
    host FM refined cut from the same start."""
    g, parts, w, corr = _seeded_case(mesh, nparts, seed)
    host, _ = refine_boundary(g, parts.copy(), nparts, weights=w,
                              sweeps=8, corridor=corr)
    fp = build_frontier_plan(g, parts, nparts, weights=w)
    out, _, _ = run_sharded_sweeps(fp, parts, nparts, sweeps=12,
                                   corridor=corr)
    assert edge_cut(g, out) <= 1.01 * edge_cut(g, host) + 1e-9


def test_stage_zero_disconnected_parts():
    """After the closing repair, no part is disconnected — the post-chain
    contract the sharded stage must honor like the host stages."""
    g, parts, w, _ = _seeded_case(box_mesh(8, 8, 6), 8, 11, frac=0.25)
    out, stats = refine_sharded_stage(g, parts, 8, weights=w)
    pm = partition_metrics(g, out, 8, weights=w)
    assert pm.disconnected_parts == 0
    assert pm.component_count == 8
    assert stats.cut_after <= stats.cut_before + 1e-9
    assert stats.cut_after == pytest.approx(edge_cut(g, out))
    assert stats.stages[0] == "refine-sharded"


def test_kway_sharded_stage_polish():
    """kway-sharded ≤ refine-sharded cut (host polish only improves)."""
    g, parts, w, _ = _seeded_case(box_mesh(8, 8, 6), 8, 13)
    out_r, _ = refine_sharded_stage(g, parts.copy(), 8, weights=w)
    out_k, stats = kway_sharded_stage(g, parts.copy(), 8, weights=w)
    assert edge_cut(g, out_k) <= edge_cut(g, out_r) + 1e-9
    assert partition_metrics(g, out_k, 8, weights=w).disconnected_parts == 0
    assert stats.stages[0] == "kway-sharded"


def test_empty_frontier_is_noop():
    """A partition along disconnected components has no cross-shard
    frontier: zero gathers, labels unchanged."""
    # two disconnected 4-cliques → parts == components → halo == 0
    src = np.array([0, 0, 0, 1, 1, 2, 4, 4, 4, 5, 5, 6])
    dst = np.array([1, 2, 3, 2, 3, 3, 5, 6, 7, 6, 7, 7])
    g = build_csr(src, dst, 8)
    parts = np.array([0, 0, 0, 0, 1, 1, 1, 1])
    fp = build_frontier_plan(g, parts, 2)
    assert fp.plan.halo == 0
    corr = balance_corridor(parts, 2, None, 0.05)
    out, records, info = run_sharded_sweeps(fp, parts, 2, sweeps=4,
                                            corridor=corr)
    assert np.array_equal(out, parts)
    assert info["gathers"] == 0 and records == []


def test_pipeline_spec_spans_and_gather_counters():
    """refine="repair+refine-sharded" through the pipeline: the
    post:refine-sharded span exists (and is part of the manifest drift
    guard's expected set), and the trace counters certify exactly one
    boundary-label all_gather per sweep."""
    from repro.obs.export import expected_span_names

    mesh = box_mesh(6, 6, 4)
    post = parse_refine("repair+refine-sharded")
    assert post == ("repair", "refine-sharded")
    pipe = PartitionPipeline(post=post)
    ctx = pipe.run(mesh, 8)
    names = {s.name for s in ctx.trace.walk()}
    assert "post:refine-sharded" in names
    assert "post:refine-sharded" in expected_span_names(ctx.config)
    counters = {}
    for s in ctx.trace.walk():
        for k, v in s.counters.items():
            counters[k] = counters.get(k, 0.0) + v
    assert counters.get("sharded_sweeps", 0) >= 1
    assert counters["sharded_gathers"] == counters["sharded_sweeps"]
    assert counters.get("halo_words", 0) > 0
    assert counters.get("halo_bytes", 0) == pytest.approx(
        4 * counters["halo_words"])
    pm = partition_metrics(ctx.require_graph(), ctx.parts, 8)
    assert pm.disconnected_parts == 0


def test_pipeline_kway_sharded_matches_quality():
    """kway-sharded through the front pipeline lands within 1% of the
    host kway chain on the same mesh."""
    mesh = box_mesh(8, 8, 6)
    cut = {}
    for spec in ("repair+kway", "kway-sharded"):
        ctx = PartitionPipeline(post=parse_refine(spec)).run(mesh, 8)
        cut[spec] = edge_cut(ctx.require_graph(), ctx.parts)
    assert cut["kway-sharded"] <= 1.01 * cut["repair+kway"] + 1e-9


def test_guard_deadline_falls_back_to_host():
    """An expired SolverGuard deadline degrades to the host FM refiner:
    output still refined + repaired, stage records the fallback."""

    class Expired:
        def expired(self):
            return True

    g, parts, w, _ = _seeded_case(box_mesh(8, 8, 6), 8, 17)
    out, stats = refine_sharded_stage(g, parts, 8, weights=w,
                                      guard=Expired())
    assert "host-fallback" in stats.stages
    assert edge_cut(g, out) <= stats.cut_before + 1e-9
    assert partition_metrics(g, out, 8, weights=w).disconnected_parts == 0


def _rejected_result_falls_back(monkeypatch, out_of):
    """Inject a sweep result ``out_of(parts)`` the stage must reject and
    check it degrades to the host FM refiner, counted in guard_fallbacks."""
    import repro.dist.refine_sharded as rs

    g, parts, w, _ = _seeded_case(box_mesh(6, 6, 4), 4, 19)
    monkeypatch.setattr(rs, "run_sharded_sweeps", lambda fp, p, *a, **k: (
        out_of(g, p), [], {"moves": 0, "gathers": 0, "cut": 0.0}))
    with obs.trace("t") as root:
        out, stats = refine_sharded_stage(g, parts, 4, weights=w)
    total = sum(s.counters.get("guard_fallbacks", 0) for s in root.walk())
    assert total >= 1
    assert "host-fallback" in stats.stages
    assert edge_cut(g, out) <= stats.cut_before + 1e-9


def test_device_path_failure_counts_guard_fallback(monkeypatch):
    """Invalid labels from the sweeps trip the guard escalation counter
    and still return a host-refined partition."""
    _rejected_result_falls_back(monkeypatch,
                                lambda g, p: np.full_like(p, 99))


def test_higher_cut_result_falls_back_to_host(monkeypatch):
    """A sweep result with a higher cut than the stage's input is rejected
    the same way (RefineRejected → host FM refiner)."""
    def worse(g, p):
        out = np.random.default_rng(0).integers(0, 4, g.n)
        assert edge_cut(g, out) > edge_cut(g, p)
        return out

    _rejected_result_falls_back(monkeypatch, worse)


def test_device_path_error_propagates(monkeypatch):
    """An exception from the device path itself (compile, lowering,
    runtime, out of memory) is a fault, not a degraded result: it reaches
    the caller and no host fallback hides it."""
    def boom(*a, **k):
        raise RuntimeError("injected device failure")

    import repro.dist.refine_sharded as rs

    g, parts, w, _ = _seeded_case(box_mesh(6, 6, 4), 4, 19)
    monkeypatch.setattr(rs, "run_sharded_sweeps", boom)
    with pytest.raises(RuntimeError, match="injected device failure"):
        refine_sharded_stage(g, parts, 4, weights=w)


def test_eight_device_parity(multi_device_run):
    """The real 8-device shard_map run reproduces the host mirror bit for
    bit, for P == D and the grouped P = 12, D = 6 case."""
    multi_device_run(r"""
import numpy as np, jax
assert len(jax.devices()) == 8
from repro.core.pipeline import PartitionPipeline
from repro.core.refine import balance_corridor, edge_cut
from repro.dist.refine_sharded import (build_frontier_plan, _pick_devices,
                                       refine_sharded_host,
                                       run_sharded_sweeps)
from repro.mesh import box_mesh

for nparts, seed, dims in ((8, 3, (8, 8, 6)), (12, 7, (9, 8, 6))):
    ctx = PartitionPipeline(bisect="rcb", post=()).run(box_mesh(*dims),
                                                       nparts)
    g = ctx.require_graph()
    rng = np.random.default_rng(seed)
    parts = ctx.parts.copy()
    sel = rng.random(g.n) < 0.12
    parts[sel] = rng.integers(0, nparts, sel.sum())
    corr = balance_corridor(parts, nparts, ctx.weights, 0.05)
    fp = build_frontier_plan(g, parts, nparts, weights=ctx.weights)
    out_d, _, info = run_sharded_sweeps(fp, parts, nparts, sweeps=10,
                                        corridor=corr)
    out_h, _, _ = refine_sharded_host(fp, parts, nparts, sweeps=10,
                                      corridor=corr)
    assert np.array_equal(out_d, out_h), (nparts, "parity")
    assert info["moves"] > 0
    print("nparts", nparts, "devices", _pick_devices(nparts),
          "cut", edge_cut(g, out_d))
""")
