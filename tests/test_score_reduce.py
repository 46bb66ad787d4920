"""JAX/Pallas score-reduce kernel (kernels/score_reduce.py): parity of the
pallas-interpret and pure-jnp ref paths against the numpy engine over seeded
random windows, edge cases (empty window, all-infeasible candidates), the
packed host/device boundary against unpacked operands bit for bit, the jit
names the benchmark's trace reducer reads, and the EcoSched engine="jax"
end-to-end wiring."""
import functools
import itertools
import re

import jax
import numpy as np
import pytest

from bench.trace import REDUCTION_MODULE
from repro.core import EcoSched, JobProfile, Node, ProfiledPerfModel, simulate
from repro.core.engine import enumerate_scored
from repro.core.perfmodel import _mk_spec
from repro.core.types import NodeView
from repro.kernels import score_reduce as sr
from repro.kernels.score_reduce import (
    score_reduce,
    score_reduce_batch,
    score_reduce_multi,
)

LAM = 0.35
TOL = 1e-6  # float32 kernel vs float64 numpy engine (ISSUE 3 acceptance)


def rand_window(seed):
    """Seeded random (specs, view): like tests/test_engine.rand_state but
    with honest fragmented free maps driven through PlacementState."""
    from repro.core import PlacementState

    rng = np.random.default_rng(seed)
    M = int(rng.choice([4, 8, 16]))
    K = int(rng.choice([2, 4]))
    W = int(rng.integers(1, 8))
    counts = [g for g in (1, 2, 3, 4, 8, 16) if g <= M]
    specs = []
    for i in range(W):
        sub = sorted(
            rng.choice(counts, size=int(rng.integers(1, len(counts) + 1)), replace=False)
        )
        t_hat = {int(g): float(100.0 / g ** rng.uniform(0.3, 1.0)) for g in sub}
        p_hat = {int(g): float(300.0 * g ** rng.uniform(0.6, 0.95)) for g in sub}
        specs.append(_mk_spec(f"j{i}", t_hat, p_hat))
    st = PlacementState(M, K)
    running = []
    for _ in range(int(rng.integers(0, K))):
        g = int(rng.integers(1, max(2, M // 2)))
        if st.can_allocate(g) and st.occupied_domains() < K:
            st.allocate(g)
            running.append(object())
    view = NodeView(
        t=0.0, total_units=M, domains=K, free_units=st.free_count(),
        running=running, free_map=list(st.free), domain_jobs=list(st.domain_jobs),
    )
    return specs, view


def reduce_case(seed, mode):
    specs, view = rand_window(seed)
    batch = enumerate_scored(specs, view, list(view.free_map), lam=LAM)
    dev, g, n = batch.padded_cols()
    scores, best = score_reduce(
        dev, g, n, lam=LAM, g_free=view.free_units, M=view.total_units, mode=mode
    )
    return batch, scores, best


@pytest.mark.parametrize("mode,seeds", [("ref", range(60)), ("interpret", range(10))])
def test_kernel_parity_vs_numpy_engine(mode, seeds):
    for seed in seeds:
        batch, scores, best = reduce_case(seed, mode)
        assert scores.shape == batch.scores.shape
        assert np.max(np.abs(scores - batch.scores)) <= TOL, seed
        # the kernel's tie-broken winner scores exactly like the engine's
        ref = batch.best_index()
        assert best >= 0
        assert abs(float(scores[best]) - float(batch.scores[ref])) <= TOL, seed
        assert batch.total_g[best] == batch.total_g[ref], seed


def test_interpret_matches_ref_bitwise():
    """Both non-TPU paths compute the identical float32 reduction."""
    for seed in range(10):
        _, s_ref, b_ref = reduce_case(seed, "ref")
        _, s_int, b_int = reduce_case(seed, "interpret")
        assert np.array_equal(s_ref, s_int), seed
        assert b_ref == b_int, seed


@pytest.mark.parametrize("mode", ["ref", "interpret"])
def test_empty_window(mode):
    view = NodeView(t=0.0, total_units=8, domains=2, free_units=8,
                    running=[], free_map=[True] * 8, domain_jobs=[0, 0])
    batch = enumerate_scored([], view, list(view.free_map), lam=LAM)
    dev, g, n = batch.padded_cols()
    scores, best = score_reduce(dev, g, n, lam=LAM, g_free=8, M=8, mode=mode)
    assert best == 0  # only the empty action exists
    assert scores[0] == pytest.approx(batch.scores[0], abs=TOL)


@pytest.mark.parametrize("mode", ["ref", "interpret"])
def test_all_infeasible_returns_sentinel(mode):
    batch, _, _ = reduce_case(3, "ref")
    dev, g, n = batch.padded_cols()
    scores, best = score_reduce(
        dev, g, n, lam=LAM, g_free=8, M=8,
        mask=np.zeros(len(batch), dtype=bool), mode=mode,
    )
    assert best == -1
    assert np.all(np.isinf(scores))


def test_mask_restricts_argmin():
    specs, view = rand_window(5)
    batch = enumerate_scored(specs, view, list(view.free_map), lam=LAM)
    dev, g, n = batch.padded_cols()
    _, best = score_reduce(dev, g, n, lam=LAM, g_free=view.free_units,
                           M=view.total_units, mode="ref")
    mask = np.ones(len(batch), dtype=bool)
    mask[best] = False
    s2, b2 = score_reduce(dev, g, n, lam=LAM, g_free=view.free_units,
                          M=view.total_units, mask=mask, mode="ref")
    assert b2 != best
    assert np.isinf(s2[best])


def test_bias_shifts_scores():
    """The bias column (EcoSched's lookahead penalty) adds elementwise."""
    specs, view = rand_window(7)
    batch = enumerate_scored(specs, view, list(view.free_map), lam=LAM)
    dev, g, n = batch.padded_cols()
    bias = np.linspace(0.0, 0.5, len(batch))
    s0, _ = score_reduce(dev, g, n, lam=LAM, g_free=view.free_units,
                         M=view.total_units, mode="ref")
    s1, _ = score_reduce(dev, g, n, lam=LAM, g_free=view.free_units,
                         M=view.total_units, bias=bias, mode="ref")
    assert np.max(np.abs((s1 - s0) - bias.astype(np.float32))) <= TOL


# ---------------------------------------------------------------------------
# Cross-node batched reduction (ISSUE 9): one launch, many nodes
# ---------------------------------------------------------------------------


def batch_cases(seeds):
    """Per-node requests + the solo-path reference results."""
    reqs, refs = [], []
    for seed in seeds:
        specs, view = rand_window(seed)
        batch = enumerate_scored(specs, view, list(view.free_map), lam=LAM)
        dev, g, n = batch.padded_cols()
        reqs.append(dict(dev=dev, g=g, n=n, lam=LAM,
                         g_free=view.free_units, M=view.total_units))
        refs.append((dev, g, n, view))
    return reqs, refs


@pytest.mark.parametrize("mode", ["ref", "interpret"])
def test_batch_matches_per_node_kernel(mode):
    """The batched kernel reproduces the solo path per node, bitwise —
    common (b_pad, s_pad) zero-padding adds exactly +0.0 per combine."""
    reqs, refs = batch_cases(range(9))
    out = score_reduce_batch(reqs, mode=mode)
    assert len(out) == len(reqs)
    for (scores, best), (dev, g, n, view) in zip(out, refs):
        s_solo, b_solo = score_reduce(
            dev, g, n, lam=LAM, g_free=view.free_units,
            M=view.total_units, mode=mode,
        )
        assert best == b_solo
        finite = np.isfinite(s_solo)
        assert np.array_equal(scores[finite], s_solo[finite])
        assert np.all(np.isinf(scores[~finite]))


@pytest.mark.parametrize("mode", ["ref", "interpret"])
def test_batch_mixed_edges(mode):
    """All-infeasible and empty-window nodes ride in the same launch as
    healthy ones without perturbing them."""
    reqs, refs = batch_cases(range(3))
    dead_mask = np.zeros(len(reqs[1]["dev"]), dtype=bool)
    reqs.insert(1, dict(reqs[1], mask=dead_mask))  # all-infeasible clone
    view = NodeView(t=0.0, total_units=8, domains=2, free_units=8,
                    running=[], free_map=[True] * 8, domain_jobs=[0, 0])
    empty = enumerate_scored([], view, list(view.free_map), lam=LAM)
    dev_e, g_e, n_e = empty.padded_cols()
    reqs.append(dict(dev=dev_e, g=g_e, n=n_e, lam=LAM, g_free=8, M=8))
    out = score_reduce_batch(reqs, mode=mode)
    assert out[1][1] == -1 and np.all(np.isinf(out[1][0]))
    assert out[-1][1] == 0  # only the empty action exists
    assert out[-1][0][0] == pytest.approx(empty.scores[0], abs=TOL)
    for (scores, best), (dev, g, n, v) in zip(
        [out[0]] + list(out[2:-1]), refs
    ):
        s_solo, b_solo = score_reduce(
            dev, g, n, lam=LAM, g_free=v.free_units, M=v.total_units,
            mode=mode,
        )
        assert best == b_solo
        finite = np.isfinite(s_solo)
        assert np.array_equal(scores[finite], s_solo[finite])


def test_batch_empty_request_list():
    assert score_reduce_batch([]) == []


@pytest.mark.parametrize("mode", ["ref", "interpret"])
def test_multi_matches_solo_per_window(mode):
    """The row-packed multi-window plane (the COMPLETE path's kernel)
    reproduces a solo ``score_reduce`` per window bitwise, including
    heterogeneous per-window f planes, biases, and λ_f."""
    reqs, _ = batch_cases(range(9))
    rng = np.random.default_rng(0)
    for k, r in enumerate(reqs):  # spice up params per window
        r["lam"] = float(0.1 + 0.1 * k)
        if k % 2 == 0:
            r["f"] = np.ones_like(r["dev"])
            r["lam_f"] = 0.25
        if k % 3 == 0:
            r["bias"] = rng.uniform(0.0, 0.5, size=len(r["dev"])).astype(
                np.float32
            )
    out = score_reduce_multi(reqs, mode=mode)
    assert len(out) == len(reqs)
    for (scores, best), r in zip(out, reqs):
        s_solo, b_solo = score_reduce(
            r["dev"], r["g"], r["n"], f=r.get("f"), lam=r["lam"],
            g_free=r["g_free"], M=r["M"], lam_f=r.get("lam_f", 0.0),
            bias=r.get("bias"), mode=mode,
        )
        assert best == b_solo
        finite = np.isfinite(s_solo)
        assert np.array_equal(scores[finite], s_solo[finite])
        assert np.all(np.isinf(scores[~finite]))


@pytest.mark.parametrize("mode", ["ref", "interpret"])
def test_multi_mixed_edges(mode):
    """Zero-row, all-masked, and healthy windows share one launch: the
    degenerate windows return -1 without perturbing their neighbours."""
    reqs, refs = batch_cases(range(3))
    dead_mask = np.zeros(len(reqs[1]["dev"]), dtype=bool)
    reqs.insert(1, dict(reqs[1], mask=dead_mask))  # all-infeasible clone
    s = reqs[0]["dev"].shape[1]
    reqs.append(  # a truly empty window: zero candidate rows
        dict(dev=np.zeros((0, s), dtype=np.float32),
             g=np.zeros((0, s), dtype=np.float32),
             n=np.zeros((0,), dtype=np.float32), lam=LAM, g_free=8, M=8)
    )
    out = score_reduce_multi(reqs, mode=mode)
    assert out[1][1] == -1 and np.all(np.isinf(out[1][0]))
    assert out[-1][1] == -1 and out[-1][0].size == 0
    for (scores, best), (dev, g, n, v) in zip(
        [out[0]] + list(out[2:-1]), refs
    ):
        s_solo, b_solo = score_reduce(
            dev, g, n, lam=LAM, g_free=v.free_units, M=v.total_units,
            mode=mode,
        )
        assert best == b_solo
        finite = np.isfinite(s_solo)
        assert np.array_equal(scores[finite], s_solo[finite])


def test_multi_empty_request_list():
    assert score_reduce_multi([]) == []


def test_batch_per_node_params_ride_in_smem():
    """Heterogeneous λ/G_free/M/λ_f rows per node in one launch: each
    node's result matches a solo call with its own scalars."""
    specs, view = rand_window(11)
    batch = enumerate_scored(specs, view, list(view.free_map), lam=LAM)
    dev, g, n = batch.padded_cols()
    f = np.ones_like(dev)
    cfgs = [
        dict(lam=0.1, g_free=2, M=4, lam_f=0.0),
        dict(lam=0.9, g_free=16, M=16, lam_f=0.25),
        dict(lam=0.35, g_free=8, M=8, lam_f=0.5),
    ]
    reqs = [dict(dev=dev, g=g, n=n, f=f, **c) for c in cfgs]
    out = score_reduce_batch(reqs, mode="ref")
    for (scores, best), c in zip(out, cfgs):
        s_solo, b_solo = score_reduce(dev, g, n, f=f, mode="ref", **c)
        assert best == b_solo
        assert np.array_equal(scores, s_solo)


# ---------------------------------------------------------------------------
# The packed boundary: one table in, one answer out, bit for bit the
# reduction of the same request from separate float32 operands
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("mode",))
def _unpacked_jit(dev, g, f, n, bias, mask, lam, gfree, m, lamf, *, mode):
    return sr._argmin(*sr._score_rows(
        dev, g, f, n, bias, mask, lam, gfree, m, lamf, mode=mode))


def unpacked(r, s_pad, mode):
    """(float32 scores, best) of request ``r`` from ten separate float32
    operands padded to ``s_pad`` slots: no table, no packed answer."""
    B, S = r["dev"].shape
    b_pad = max(256, 1 << max(B - 1, 0).bit_length())

    def plane(a):
        out = np.zeros((b_pad, s_pad), dtype=np.float32)
        if a is not None:
            out[:B, :S] = a
        return out

    def col(a, pad=0.0):
        out = np.full((b_pad, 1), pad, dtype=np.float32)
        if a is not None:
            out[:B, 0] = np.broadcast_to(np.asarray(a, dtype=np.float32), (B,))
        return out

    mask = r.get("mask")
    scores, best = _unpacked_jit(
        plane(r["dev"]), plane(r["g"]), plane(r.get("f")), col(r["n"]),
        col(r.get("bias")), col(np.ones(B) if mask is None else mask),
        col(r["lam"]), col(r["g_free"]), col(r["M"], pad=1.0),
        col(r.get("lam_f", 0.0)), mode=mode,
    )
    return np.asarray(scores)[:B], int(best)


def request(seed, B, S=5, *, f=False, bias=False, mask=False, **params):
    rng = np.random.default_rng(seed)
    r = dict(dev=rng.random((B, S)), g=rng.integers(0, 4, (B, S)).astype(float),
             n=rng.integers(0, S + 1, B), lam=0.35, g_free=8, M=8)
    if f:
        r["f"] = rng.integers(0, 4, (B, S)).astype(float)
        r["lam_f"] = 0.25
    if bias:
        r["bias"] = rng.uniform(0.0, 0.5, B)
    if mask:
        r["mask"] = rng.random(B) < 0.7
    r.update(params)
    return r


def last_row_wins(B):
    """B rows whose last row alone scores lowest."""
    r = request(40, B)
    r["dev"] = np.ones_like(r["dev"])
    r["dev"][-1] = 0.0
    return r


BOUNDARY_CASES = {
    **{f"f{int(f)}-bias{int(b)}-mask{int(m)}":
       [request(k, 7 + 3 * k, f=f, bias=b, mask=m) for k in range(3)]
       for f, b, m in itertools.product([False, True], repeat=3)},
    "empty-windows": [request(1, 0), request(2, 9, f=True), request(3, 0),
                      request(4, 4, bias=True)],
    "all-infeasible": [request(5, 6, mask=True) | dict(mask=np.zeros(6, bool)),
                       request(6, 11),
                       request(7, 3) | dict(mask=np.zeros(3, bool))],
    # the winner on the table's last row, b_pad - 1: at 255 of 256 rows for
    # every entry point, then at the last packed row of a multi launch
    "winner-last-row": [last_row_wins(256)],
    "winner-last-packed-row": [request(8, 56), last_row_wins(200)],
    "heterogeneous-params": [
        request(9 + k, 12, S=3 + k, f=True, lam=lam, g_free=gf, M=M, lam_f=lf)
        for k, (lam, gf, M, lf) in enumerate(
            [(0.1, 2, 4, 0.0), (0.9, 16, 16, 0.25), (0.35, 8, 8, 0.5),
             (0.6, 0, 12, 0.125)])],
}


def assert_bitwise(got, want):
    (scores, best), (ref_scores, ref_best) = got, want
    assert scores.dtype == np.float32 and scores.shape == ref_scores.shape
    assert np.array_equal(scores.view(np.uint32), ref_scores.view(np.uint32))
    assert type(best) is int and best == ref_best


@pytest.mark.parametrize("mode", ["ref", "interpret"])
@pytest.mark.parametrize("case", list(BOUNDARY_CASES))
def test_packed_boundary_is_bitwise_unpacked(case, mode):
    reqs = BOUNDARY_CASES[case]
    for r in reqs:
        kw = {k: v for k, v in r.items() if k not in ("dev", "g", "n")}
        s_pad = sr._pads(*r["dev"].shape)[1]
        assert_bitwise(score_reduce(r["dev"], r["g"], r["n"], mode=mode, **kw),
                       unpacked(r, s_pad, mode))
    s_pad = sr._pads(1, max(r["dev"].shape[1] for r in reqs))[1]
    for entry in (score_reduce_batch, score_reduce_multi):
        out = entry(reqs, mode=mode)
        assert len(out) == len(reqs)
        for got, r in zip(out, reqs):
            assert_bitwise(got, unpacked(r, s_pad, mode))
    if case in ("empty-windows", "all-infeasible"):
        assert [b for _, b in score_reduce_multi(reqs, mode=mode)].count(-1) == 2
    if case == "winner-last-row":
        assert score_reduce(*(reqs[0][k] for k in ("dev", "g", "n")), lam=0.35,
                            g_free=8, M=8, mode=mode)[1] == 255


def test_pack_refuses_rows_float32_cannot_index():
    with pytest.raises(ValueError, match="float32"):
        sr._pack([], [], 1 << 24, 8, dummy=0)


def test_jit_module_names_are_what_the_trace_reducer_reads():
    """``bench/trace.py`` times the three reductions by their module names
    for ``score_reduce_roofline``: a rename would silence it on the chip."""
    def shape(*s):
        return jax.ShapeDtypeStruct(s, np.float32)

    lowered = [
        sr._reduce_jit.lower(shape(256, 32), mode="ref"),
        sr._reduce_batch_jit.lower(shape(2, 256, 32), mode="ref"),
        sr._reduce_multi_jit.lower(shape(256, 32), n_windows=4, mode="ref"),
    ]
    names = [re.search(r"^module @([\w.]+)", low.as_text(), re.M).group(1)
             for low in lowered]
    assert names == ["jit__reduce_jit", "jit__reduce_batch_jit",
                     "jit__reduce_multi_jit"]
    assert all(REDUCTION_MODULE.match(n) for n in names)


def test_engine_jax_end_to_end_matches_vector():
    """EcoSched(engine="jax") reproduces the vector backend's schedule."""
    truth = {
        name: JobProfile(
            name=name,
            runtime={1: t, 2: t / 1.8, 3: t / 2.4, 4: t / 2.8},
            busy_power={1: p, 2: 1.9 * p, 3: 2.7 * p, 4: 3.4 * p},
        )
        for name, t, p in [
            ("a", 100.0, 100.0), ("b", 200.0, 120.0), ("c", 50.0, 90.0),
            ("d", 140.0, 105.0), ("e", 90.0, 115.0),
        ]
    }
    node = Node(units=4, domains=2, idle_power_per_unit=10.0)
    kw = dict(lam=0.4, tau=0.5)
    r_jax = simulate(
        EcoSched(ProfiledPerfModel(truth, noise=0.02, seed=3), engine="jax", **kw),
        node, truth, queue=list(truth),
    )
    r_vec = simulate(
        EcoSched(ProfiledPerfModel(truth, noise=0.02, seed=3), engine="vector", **kw),
        node, truth, queue=list(truth),
    )
    assert [(r.job, r.g, r.start, r.domain) for r in r_jax.records] == [
        (r.job, r.g, r.start, r.domain) for r in r_vec.records
    ]
    assert r_jax.total_energy == r_vec.total_energy
