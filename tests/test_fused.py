"""Device epoch engine (``fused.drive_lanes_bucketed``) vs the
sequential oracle.

The contract (core/fused.py): every lane's result is bitwise-equal to
``sim.drive_lane`` — integer LLC stat counters and float64 timing
histories alike, checked through ``_reference.assert_bitwise`` (the
timing update runs on the host's own float64, in the host's operation
order).  Covers way partitioning, SHIP bypass, DPCP prefetch, the
deadline switch, HyDRA/APM modulation, online-LERN retrain boundaries,
the round-capacity overflow demotion to the host loop, and a hypothesis
property over random short traces.
"""
import dataclasses
import math

import numpy as np
import pytest

from _reference import assert_bitwise, run_reference
from repro.core import cores as cores_mod
from repro.core import fused, llc, policies, sim, sweep
from repro.core.tracegen import Trace

TINY = dataclasses.replace(sim.SimParams(), n_inputs=1, max_epochs=40,
                           subsample_target=50_000)
DEADLINE = 2.0e6  # explicit: skips the calibration run, keeps tests fast


@pytest.fixture
def on_device(monkeypatch):
    """Lane groups ``simulate_group(engine="auto")`` hands the device
    driver: a group it found ineligible would run the host loop and make
    a parity check against the host oracle vacuous."""
    groups = []
    orig = fused.drive_lanes_bucketed

    def spy(gs, *a, **kw):
        groups.extend(gs)
        return orig(gs, *a, **kw)

    monkeypatch.setattr(fused, "drive_lanes_bucketed", spy)
    return groups


# ---------------------------------------------------------------------------
# policy-family parity vs the sequential oracle
# ---------------------------------------------------------------------------
POLS = [
    policies.get("fifo-nb"),
    policies.get("arp-cs-as"),            # SHIP bypass
    policies.get("arp-cs-asth0.3-d"),     # §III-C1 deadline switch
    policies.get("dpcp"),                 # prefetch + 1-way partition
    policies.get("hydra"),                # LERN + APM modulation
    policies.with_way_partition(policies.get("arp-cs-as"), 0xFF00, 0x00FF),
]


@pytest.mark.parametrize("mix", ["moti1", "moti2"])
def test_fused_matches_oracle_across_policies(mix, on_device):
    grp = sweep.simulate_group("config1", mix, POLS, TINY,
                               deadline_cycles=DEADLINE, engine="auto")
    assert sum(map(len, on_device)) == len(POLS)
    for pol, got in zip(POLS, grp):
        want = run_reference("config1", mix, pol, TINY,
                             deadline_cycles=DEADLINE)
        assert_bitwise(got, want, (mix, pol.name))


def test_fused_multi_input_cycling(on_device):
    """Input completions, the inter-input wait, and the periodic arrival
    schedule all live in the scan carry — run several inputs through."""
    p = dataclasses.replace(TINY, n_inputs=3, max_epochs=120)
    pol = policies.get("arp-cas")
    got = sweep.simulate_group("config1", "moti2", [pol], p,
                               deadline_cycles=DEADLINE, engine="auto")[0]
    want = run_reference("config1", "moti2", pol, p,
                         deadline_cycles=DEADLINE)
    assert len(got.completion_cycles) == 3 and on_device
    assert_bitwise(got, want, "multi-input")


def test_fused_online_lern_retrain_boundary(on_device):
    """Finite retrain periods cut super-steps at the refit boundary; the
    host hook runs and the re-uploaded tables must keep the fused lane
    bitwise with the sequential oracle."""
    p = dataclasses.replace(TINY, max_epochs=30)
    pol = dataclasses.replace(policies.get("arp-al-ol"), retrain_period=5)
    got = sweep.simulate_group("config1", "moti1", [pol], p,
                               deadline_cycles=DEADLINE, engine="auto")[0]
    want = run_reference("config1", "moti1", pol, p,
                         deadline_cycles=DEADLINE)
    assert_bitwise(got, want, "online-lern")
    assert on_device


def test_fused_online_lern_infinite_period_degenerates(on_device):
    ol_inf = dataclasses.replace(policies.get("arp-al-ol"),
                                 retrain_period=math.inf)
    grp = sweep.simulate_group("config1", "moti1",
                               [policies.get("arp-al"), ol_inf], TINY,
                               deadline_cycles=DEADLINE, engine="auto")
    assert_bitwise(grp[1], grp[0], "ol-inf")
    assert sum(map(len, on_device)) == 2


# ---------------------------------------------------------------------------
# overflow fallback + engine selection
# ---------------------------------------------------------------------------
def test_fused_overflow_falls_back_to_host(monkeypatch):
    """A round-capacity overflow past the cap must finish the lane on the
    host loop from its frozen state — exercised deliberately by pinning
    the capacity below the trace's hot-set depth."""
    calls = []
    orig = fused.drive_lane

    def spy(lane, state=None):
        calls.append(lane.epoch)
        return orig(lane, state=state)

    monkeypatch.setattr(fused, "drive_lane", spy)
    monkeypatch.setattr(fused, "MAX_ROUNDS_CAP", 16)
    pol = policies.get("arp-cs")
    art = sim.load_artifacts("config1", "moti1", TINY, True)
    lane = sim.Lane("config1", "moti1", pol, TINY, sim.DDR3_1600,
                    DEADLINE, art, True)
    fused.drive_lanes_bucketed([[lane]], k_epochs=4, max_rounds=8)
    got = lane.result()
    assert len(calls) == 1, "overflow demotion never fired"
    want = run_reference("config1", "moti1", pol, TINY, sim.DDR3_1600,
                         deadline_cycles=DEADLINE)
    assert_bitwise(got, want, "overflow-fallback")


def test_fused_sparse_and_dense_rounds_agree(monkeypatch, on_device):
    """The hybrid dense/sparse round branch is internal: forcing every
    round dense must not change anything."""
    pol = policies.get("arp-cs-as")
    got = sweep.simulate_group("config1", "moti1", [pol], TINY,
                               deadline_cycles=DEADLINE, engine="auto")[0]
    monkeypatch.setattr(fused, "SPARSE_CAP", 0)
    dense = sweep.simulate_group("config1", "moti1", [pol], TINY,
                                 deadline_cycles=DEADLINE, engine="auto")[0]
    assert_bitwise(dense, got, "sparse-vs-dense")
    assert len(on_device) == 2


def test_fused_occupancy_recording(on_device):
    """record_occupancy lanes are fused-eligible: the per-epoch [2]
    core/accel occupancy counters ride the scan outputs and must match
    the host loop's llc.occupancy reads exactly."""
    p = dataclasses.replace(TINY, record_occupancy=True, max_epochs=20)
    pol = policies.get("arp-cs-as")
    got = sweep.simulate_group("config1", "moti1", [pol], p,
                               deadline_cycles=DEADLINE, engine="auto")[0]
    want = run_reference("config1", "moti1", pol, p,
                         deadline_cycles=DEADLINE)
    assert_bitwise(got, want, "occupancy")
    assert got.occupancy and got.occupancy == want.occupancy
    assert on_device


def test_engine_selection_and_gate(on_device):
    # engine="host" never enters the device driver
    sweep.simulate_group("config1", "moti1", [policies.get("fifo-nb")],
                         TINY, deadline_cycles=DEADLINE, engine="host")
    assert on_device == []
    # the per-group "fused" engine is gone: like any unknown name it raises
    for engine in ("fused", "nope"):
        with pytest.raises(ValueError):
            sweep.simulate_group("config1", "moti1",
                                 [policies.get("fifo-nb")], TINY,
                                 deadline_cycles=DEADLINE, engine=engine)


def test_occupancy_single_fetch():
    """llc.occupancy counts (one stacked device fetch) match numpy."""
    cfg = llc.LLCConfig(size_bytes=64 * 64 * 4, ways=4)
    state = llc.init_state(cfg)
    import jax.numpy as jnp
    tags = np.full((cfg.num_sets, cfg.ways), -1, np.int32)
    owner = np.zeros_like(tags)
    tags[0, :3] = [1, 2, 3]
    owner[0, 1] = 1
    tags[5, 0] = 9
    owner[5, 0] = 1
    state = state._replace(tags=jnp.asarray(tags), owner=jnp.asarray(owner))
    assert llc.occupancy(state) == (2, 2)


# ---------------------------------------------------------------------------
# hypothesis: random short traces, no-LERN policy families
# ---------------------------------------------------------------------------
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # optional test extra; CI installs it
    HAVE_HYPOTHESIS = False

    def given(**kw):  # noqa: D103 - placeholder so the decorator parses
        def deco(fn):
            return pytest.mark.skip(reason="hypothesis not installed")(fn)
        return deco

    def settings(**kw):
        def deco(fn):
            return fn
        return deco

    class st:  # noqa: D101
        @staticmethod
        def integers(*a, **kw):
            return None

HP = dataclasses.replace(sim.SimParams(), n_inputs=1, max_epochs=12,
                         accel_epoch_cap=400, subsample_target=50_000)
HPOLS = [policies.get(n) for n in
         ("fifo-nb", "arp-cs-as", "dpcp", "arp-cs-afr0.6", "flash")]


def _synthetic_artifacts(seed: int, n_lines: int, length: int) -> sim.Artifacts:
    rng = np.random.default_rng(seed)
    line = rng.integers(0, n_lines, length).astype(np.int64)
    tr = Trace(line=line, write=rng.random(length) < 0.3,
               cycle=np.arange(length, dtype=np.int64),
               layer=np.zeros(length, np.int32), layer_names=["l0"],
               compute_cycles=length)
    profiles = [cores_mod.PROFILES[b] for b in cores_mod.MIXES["moti2"]]
    est = [max(1024, cores_mod.epoch_accesses(pr, pr.ipc0,
                                              float(HP.epoch_cycles))
               * HP.max_epochs) for pr in profiles]
    streams = [cores_mod.generate_stream_fast(pr, est[k], k, seed=HP.seed)
               .astype(np.int64) for k, pr in enumerate(profiles)]
    return sim.Artifacts(trace=tr, profiles=profiles, est=est,
                         streams=streams)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       n_lines=st.integers(8, 6000),
       length=st.integers(16, 4000),
       pol_idx=st.integers(0, len(HPOLS) - 1))
def test_fused_property_random_traces(seed, n_lines, length, pol_idx):
    art = _synthetic_artifacts(seed, n_lines, length)
    pol = HPOLS[pol_idx]

    def mk():
        return sim.Lane("synthetic", "moti2", pol, HP, sim.DDR3_1600,
                        DEADLINE, art, True)

    want = sim.drive_lane(mk())
    lane = mk()
    fused.drive_lanes_bucketed([[lane]])
    assert_bitwise(lane.result(), want, (seed, n_lines, length, pol.name))


# ---------------------------------------------------------------------------
# host-call operand packing: one uint32 row carries every operand kind
# ---------------------------------------------------------------------------
_SPECIALS = np.array([0.0, -0.0, np.inf, -np.inf, np.nan, 5e-324,
                      np.nextafter(1.0, 2.0), np.nextafter(1.0, 0.0),
                      -np.nextafter(0.0, 1.0), 2.2250738585072014e-308,
                      1.0 / 3.0, -7.5])


def _host_call_operands(n_lanes: int, n_cores: int = 8):
    """Operands of every kind ``_begin_lane``/``_finish_lane`` send, as
    (per-lane batched values, unbatched values)."""
    rng = np.random.default_rng(7)

    def f64(shape, lead=()):
        return fused._bits(np.resize(rng.permutation(_SPECIALS),
                                     lead + shape))

    lanes = (n_lanes,)
    batched = {
        "flag": np.array([True, False, True][:n_lanes]),
        "ri_th": np.array([-1, 3, 0][:n_lanes], np.int32),
        "rc_sel": np.full(lanes + (n_cores,), -1, np.int32),
        "now": f64((), lanes),
        "ipc": f64((n_cores,), lanes),
        "t_a": f64((4,), lanes),
        "bands": f64((7,), lanes),
        "big": np.array([2**40 + 3, -5, 0][:n_lanes], np.int64),
    }
    unbatched = {
        "hydra": np.array(True),
        "pos": np.array(-1, np.int32),
        "stats": np.arange(-4, 4, dtype=np.int32).reshape(4, 2),
        "et": f64(()),
        "nominal": f64((n_cores,)),
        "sched_nd": np.asarray(fused._words(np.array(
            [2**33 + 1, 7, 0, 2**62], np.int64))),
        "completions": f64((8,)),
    }
    return batched, unbatched


@pytest.mark.parametrize("batched_lanes", [3, 0])
def test_host_call_packed_row_round_trips_bitwise(batched_lanes):
    """``_host_call`` ships a call's operands as one packed uint32 row;
    ``fn`` must receive exactly what a per-operand ``pure_callback``
    hands over (broadcast to the lane batch), for bool, int32 (with -1),
    64-bit integers and ``f64b`` pairs of 0.0, -0.0, +-inf, NaN,
    subnormals and ``nextafter`` neighbours, batched and unbatched under
    ``vmap_method="expand_dims"`` — and the results must come back
    intact."""
    import jax
    import jax.numpy as jnp

    n = max(batched_lanes, 1)
    with jax.enable_x64():
        batched, unbatched = _host_call_operands(n)
        if not batched_lanes:        # every operand unbatched, no vmap
            unbatched.update({k: v[0] for k, v in batched.items()})
            batched = {}
        shapes = {k: np.shape(v)[1:] for k, v in batched.items()}
        shapes.update({k: np.shape(v) for k, v in unbatched.items()})
        ranks = {k: len(s) for k, s in shapes.items()}
        got, ref = [], []

        def fn(a):
            got.append(a)
            return {"flag": a["hydra"], "ri_th": a["pos"],
                    "ipc": a["nominal"], "now": a["et"]}

        out = {"flag": jax.ShapeDtypeStruct((), bool),
               "ri_th": jax.ShapeDtypeStruct((), jnp.int32),
               "ipc": fused._f64((8,)), "now": fused._f64()}

        def per_operand(a):
            a = {k: np.asarray(v) for k, v in a.items()}
            ref.append(a)
            lead = np.broadcast_shapes(
                *(v.shape[:v.ndim - ranks[k]] for k, v in a.items()))
            return np.zeros(lead, np.uint32)

        def step(b, u):
            args = {**b, **u}
            zero = jax.pure_callback(
                per_operand, jax.ShapeDtypeStruct((), jnp.uint32), args,
                vmap_method="expand_dims")
            return fused._host_call(fn, out, args), zero

        dev_u = jax.tree.map(jnp.asarray, unbatched)
        if batched_lanes:
            res = jax.jit(jax.vmap(step, in_axes=(0, None)))(
                jax.tree.map(jnp.asarray, batched), dev_u)
        else:
            res = jax.jit(step)({}, dev_u)
        res = jax.tree.map(np.asarray, res[0])

    (a,), (old,) = got, ref
    assert sorted(a) == sorted(shapes)
    for k, v in a.items():
        sent = batched[k] if k in batched else unbatched[k]
        assert v.shape == (n,) + shapes[k], k
        assert v.dtype == sent.dtype, k
        assert v.tobytes() == np.broadcast_to(sent, v.shape).tobytes(), k
        if sent.dtype.itemsize == 8:
            # the per-operand path narrows 64-bit operands in a callback
            # thread where 64-bit mode is off: none is sent that way
            continue
        w = np.broadcast_to(old[k].reshape(
            (-1,) + old[k].shape[old[k].ndim - ranks[k]:]), v.shape)
        assert v.dtype == w.dtype, k
        assert v.tobytes() == np.ascontiguousarray(w).tobytes(), k

    lead = (n,) if batched_lanes else ()
    assert res["flag"].shape == lead and res["flag"].all()
    assert (res["ri_th"] == -1).all()
    for k, src in (("ipc", "nominal"), ("now", "et")):
        want_bits = np.broadcast_to(unbatched[src],
                                    lead + unbatched[src].shape)
        assert res[k].tobytes() == want_bits.tobytes(), k
