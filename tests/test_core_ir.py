"""StitchIR, pattern generation, ILP, cycle cuts, cost model."""

import numpy as np
import pytest

from repro.core import (
    CostModel, FusionPattern, GenConfig, Graph, GraphBuilder, ILPSolver,
    OpKind, ReduceKind, TPU_V5E, V100, contraction_creates_cycle,
    exploratory_fusion, generate_patterns, multi_step_substitution,
    solve_fusion_plan, substitution_fusion,
)
from conftest import make_mlp_norm_graph, make_softmax_graph


# ---------------------------------------------------------------- IR --------

def test_graph_topo_and_validate():
    g, x, y = make_softmax_graph()
    topo = g.topo_order()
    pos = {n: i for i, n in enumerate(topo)}
    for node in g.nodes.values():
        for o in node.operands:
            assert pos[o] < pos[node.name]


def test_cycle_detection_in_builder():
    g = Graph("bad")
    from repro.core.ir import OpNode
    g.add(OpNode("a", OpKind.PARAMETER, (2,), "float32"))
    with pytest.raises(ValueError):
        g.add(OpNode("b", OpKind.ELEMENTWISE, (2,), "float32", ("missing",)))


def test_reduce_kind_classification():
    b = GraphBuilder("r")
    x = b.param("x", (8, 16, 32))
    row = b.reduce("sum", x, axes=(2,))
    col = b.reduce("sum", x, axes=(0,))
    sca = b.reduce("sum", x, axes=(0, 1, 2))
    g = b.build(outputs=[row, col, sca])
    assert g[row].reduce_kind is ReduceKind.ROW
    assert g[col].reduce_kind is ReduceKind.COLUMN
    assert g[sca].reduce_kind is ReduceKind.SCALAR


def test_external_io_and_saved_bytes():
    g, x, y = make_softmax_graph(rows=4, cols=8)
    members = frozenset(n for n in g.nodes if n != x)
    p = FusionPattern(g, members)
    assert p.external_inputs == [x]
    assert p.external_outputs == [y]
    # every intermediate is internal: 5 tensors saved x 2 (write+read)
    internal = [n for n in members if n != y]
    expected = 2 * sum(g[n].bytes for n in internal)
    assert p.saved_bytes == expected


# ------------------------------------------------------- pattern gen --------

def test_substitution_collapses_between_partitions():
    g = make_mlp_norm_graph()
    partition = {n.name for n in g.nodes.values() if n.kind is OpKind.GEMM}
    pats = substitution_fusion(g, partition)
    # everything after the dot collapses into one pattern
    assert len(pats) == 1
    assert not any("dot" in m for m in pats[0].members)


def test_multi_step_widening_fuses_gemm_eventually():
    g = make_mlp_norm_graph()
    pats = multi_step_substitution(g, GenConfig())
    assert any(any("dot" in m for m in p.members) for p in pats), \
        "later widening steps must allow small-gemm fusion"


def test_exploratory_no_cycles_and_fusible_kinds():
    g = make_mlp_norm_graph()
    cfg = GenConfig(seed_min_bytes=1024)
    pats = exploratory_fusion(g, None, cfg)
    assert pats, "exploratory fusion found nothing"
    for p in pats:
        assert not p.creates_cycle()
        for n in p.nodes:
            # small GEMMs (below large_gemm_flops) are explorable alongside
            # the classic fusible kinds; custom kernels never are
            assert n.kind in (
                OpKind.ELEMENTWISE, OpKind.BROADCAST, OpKind.RESHAPE,
                OpKind.TRANSPOSE, OpKind.REDUCTION, OpKind.BATCHED_GEMM,
                OpKind.GEMM)
            if n.kind is OpKind.GEMM:
                from repro.core.fusiongen import _gemm_flops
                assert _gemm_flops(g, n) < cfg.large_gemm_flops


def test_exploratory_excludes_large_gemms():
    g = make_mlp_norm_graph()
    # with the threshold at zero every GEMM is "large" -> never explored
    cfg = GenConfig(seed_min_bytes=1024, large_gemm_flops=0.0)
    for p in exploratory_fusion(g, None, cfg):
        assert not any(n.kind is OpKind.GEMM for n in p.nodes)


def test_contraction_cycle_detection():
    # a -> b -> c ; fusing {a, c} creates a cycle through b
    b = GraphBuilder("cyc")
    x = b.param("x", (4,))
    a = b.ew("exp", x)
    mid = b.ew("neg", a)
    c = b.ew("add", a, mid)
    g = b.build(outputs=[c])
    assert contraction_creates_cycle(g, {a, c})
    assert not contraction_creates_cycle(g, {a, mid, c})


# ---------------------------------------------------------------- ILP -------

def test_ilp_simple_packing():
    # items 0,1 conflict; 2 independent. weights favor 1+2.
    solver = ILPSolver([3.0, 4.0, 2.0], [{1}, {0}, set()])
    sel, val = solver.solve()
    assert sel == [1, 2] and val == 6.0


def test_ilp_cut_constraint():
    solver = ILPSolver([3.0, 4.0, 2.0], [set(), set(), set()])
    solver.add_cut(frozenset({0, 1, 2}))
    sel, val = solver.solve()
    assert val == 7.0 and len(sel) == 2


def test_ilp_matches_pulp_on_random_instances(rng):
    pulp = pytest.importorskip("pulp")
    for trial in range(5):
        n = 12
        w = [float(x) for x in rng.uniform(0.1, 5.0, n)]
        overlaps = [set() for _ in range(n)]
        for i in range(n):
            for j in range(i + 1, n):
                if rng.random() < 0.3:
                    overlaps[i].add(j)
                    overlaps[j].add(i)
        sel, val = ILPSolver(w, overlaps).solve()
        # pulp reference
        prob = pulp.LpProblem("sp", pulp.LpMaximize)
        xs = [pulp.LpVariable(f"x{i}", cat="Binary") for i in range(n)]
        prob += pulp.lpSum(w[i] * xs[i] for i in range(n))
        for i in range(n):
            for j in overlaps[i]:
                if i < j:
                    prob += xs[i] + xs[j] <= 1
        prob.solve(pulp.PULP_CBC_CMD(msg=0))
        ref = pulp.value(prob.objective)
        assert abs(val - ref) < 1e-6, f"trial {trial}: {val} vs pulp {ref}"


def test_plan_is_disjoint_and_acyclic():
    g = make_mlp_norm_graph()
    pats = generate_patterns(g)
    cost = CostModel()
    scores = [cost.score(p).score for p in pats]
    res = solve_fusion_plan(g, pats, scores)
    seen = set()
    for p in res.chosen:
        assert not (p.members & seen), "plan patterns overlap"
        seen |= p.members
    from repro.core.ilp import _find_cycle_patterns
    assert _find_cycle_patterns(g, res.chosen) is None


def test_pack_member_exclusivity_never_double_covers():
    """A PackPattern and vertical patterns over its member subgraphs are
    mutually exclusive in every ILP solution — whichever wins, no node is
    ever covered by two chosen patterns."""
    from repro.core.pattern import PackPattern

    b = GraphBuilder("excl")
    p0 = b.param("p0", (8, 64))
    p1 = b.param("p1", (8, 64))
    a1 = b.ew("exp", p0)
    a2 = b.ew("neg", a1)
    c1 = b.ew("exp", p1)
    c2 = b.ew("neg", c1)
    g = b.build(outputs=[a2, c2])
    pack = PackPattern(g, frozenset({a1, a2, c1, c2}), "manual",
                       member_groups=(frozenset({a1, a2}),
                                      frozenset({c1, c2})))
    pats = [pack,
            FusionPattern(g, frozenset({a1, a2}), "manual"),
            FusionPattern(g, frozenset({c1, c2}), "manual")]
    for scores in ([3.0, 2.0, 2.0], [1.0, 2.0, 2.0], [5.0, 1.0, 1.0]):
        res = solve_fusion_plan(g, pats, list(scores))
        seen = set()
        for p in res.chosen:
            assert not (p.members & seen), "node double-covered"
            seen |= p.members
        if any(getattr(p, "member_groups", None) for p in res.chosen):
            # the pack covers everything: nothing else may co-select
            assert len(res.chosen) == 1


def test_pack_pairwise_cycle_is_hard_exclusion():
    """A pack and a vertical pattern that close a cycle only when BOTH are
    contracted (P -> Q and Q -> P through different member pairs) are
    mutually excluded up front — the plan stays acyclic and keeps the
    better-scoring of the two."""
    from repro.core.ilp import _find_cycle_patterns
    from repro.core.pattern import PackPattern

    b = GraphBuilder("paircyc")
    p0 = b.param("p0", (8, 64))
    p1 = b.param("p1", (8, 64))
    a = b.ew("exp", p0)      # pack member 1
    q1 = b.ew("neg", a)      # vertical member (consumes pack)
    q2 = b.ew("relu", p1)    # vertical member (feeds pack)
    d = b.ew("tanh", q2)     # pack member 2
    g = b.build(outputs=[q1, d])
    pack = PackPattern(g, frozenset({a, d}), "manual",
                       member_groups=(frozenset({a}), frozenset({d})))
    vert = FusionPattern(g, frozenset({q1, q2}), "manual")
    assert _find_cycle_patterns(g, [pack]) is None
    assert _find_cycle_patterns(g, [vert]) is None
    assert _find_cycle_patterns(g, [pack, vert]) is not None
    for scores in ([2.0, 1.0], [1.0, 2.0]):
        res = solve_fusion_plan(g, [pack, vert], list(scores))
        assert len(res.chosen) == 1
        assert _find_cycle_patterns(g, res.chosen) is None
        assert res.objective == max(scores)


# ---------------------------------------------------------- cost model ------

def test_cost_model_monotonic_bandwidth():
    hw = TPU_V5E
    assert hw.mem_time(1 << 20) < hw.mem_time(1 << 24)
    assert hw.efficiency(1 << 10) < hw.efficiency(1 << 26) <= 1.0


def test_score_positive_for_classic_stitch():
    g, x, y = make_softmax_graph(rows=1024, cols=1024)
    members = frozenset(n for n in g.nodes if n != x)
    p = FusionPattern(g, members)
    for hw in (V100, TPU_V5E):
        s = CostModel(hw).score(p)
        assert s.feasible and s.score > 0


def test_score_rejects_over_budget():
    # column reduction -> (4M,) intermediate consumed in-kernel: its scratch
    # tile is the whole 16MB row, far over V100's 96KB shared budget.
    b = GraphBuilder("big")
    x = b.param("x", (64, 1 << 22))
    r = b.reduce("sum", x, axes=(0,))
    rb = b.bcast(r, (64, 1 << 22), (1,))
    y = b.ew("div", x, rb)
    g = b.build(outputs=[y])
    p = FusionPattern(g, frozenset([r, rb, y]))
    s = CostModel(V100).score_model_based(p)
    assert not s.feasible and "budget" in s.reason
