"""Chaos harness + guarded runtime: fault classification, guarded
retries, degradation ladder, EOS masking, replay-deterministic
sampling, decode-state checkpoint/resume, and the chaos matrix's
recovered-bit-identical guarantees."""
import json
import os
import signal
import subprocess
import sys
import textwrap

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.runtime.chaos import (ChaosInjector, FaultPlan,  # noqa: E402
                                 FaultSpec, corrupt_tune_cache,
                                 tear_checkpoint)
from repro.runtime.guard import (Backoff, DegradationLadder,  # noqa: E402
                                 FailureReport, GuardedCall,
                                 GuardExhausted, ServerState,
                                 TransientFault, ValidationError,
                                 classify_error, sample_key, spot_check,
                                 validate_finite)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 4):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


# ---------------------------------------------------------------------------
# classification / backoff / validation
# ---------------------------------------------------------------------------

def test_classify_error_taxonomy():
    from jax.errors import JaxRuntimeError
    assert classify_error(TransientFault("x")) == "transient"
    assert classify_error(ValidationError("nan")) == "transient"
    assert classify_error(TimeoutError()) == "transient"
    assert classify_error(ConnectionError()) == "transient"
    # XLA runtime errors: transient unless compile/shape-family
    assert classify_error(
        JaxRuntimeError("UNAVAILABLE: socket closed")) == "transient"
    assert classify_error(
        JaxRuntimeError("INVALID_ARGUMENT: shape mismatch")) == "fatal"
    # generic RuntimeErrors: fatal unless a transient marker
    assert classify_error(RuntimeError("RESOURCE_EXHAUSTED: oom")) == \
        "transient"
    assert classify_error(RuntimeError("boom")) == "fatal"
    # a run-time allocation failure may clear; a compiler's refusal to
    # fit the program into HBM / SMEM fails the same way on every retry
    assert classify_error(JaxRuntimeError(
        "RESOURCE_EXHAUSTED: Error allocating device buffer")) == \
        "transient"
    assert classify_error(JaxRuntimeError(
        "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
        "memory in memory space hbm. Used 28.55G of 15.75G hbm.")) == \
        "fatal"
    assert classify_error(JaxRuntimeError(
        "RESOURCE_EXHAUSTED: Allocation (size=1122304) would exceed "
        "memory (size=1048576) :: 'prefetched SMEM operand 0'")) == "fatal"
    # programming errors never retry
    assert classify_error(ValueError("shape")) == "fatal"
    assert classify_error(TypeError()) == "fatal"
    assert classify_error(KeyError("k")) == "fatal"


def test_backoff_deterministic_and_bounded():
    a = Backoff(base_s=0.1, factor=2.0, max_s=0.5, jitter=0.5, seed=7)
    b = Backoff(base_s=0.1, factor=2.0, max_s=0.5, jitter=0.5, seed=7)
    da = [a.delay(i) for i in range(1, 8)]
    db = [b.delay(i) for i in range(1, 8)]
    assert da == db                       # seeded => replayable schedule
    for i, d in enumerate(da, start=1):
        raw = min(0.1 * 2.0 ** (i - 1), 0.5)
        assert 0.5 * raw <= d <= 1.5 * raw
    c = Backoff(base_s=0.1, jitter=0.5, seed=8)
    assert [c.delay(i) for i in range(1, 8)] != da  # decorrelated


def test_validate_finite_and_spot_check():
    validate_finite({"a": jnp.ones(3), "b": np.arange(4)})
    with pytest.raises(ValidationError, match="non-finite"):
        validate_finite({"x": {"y": np.array([1.0, np.nan])}})
    with pytest.raises(ValidationError):
        validate_finite(np.array([np.inf]))
    ref = {"w": np.arange(6, dtype=np.float32)}
    spot_check(ref)(dict(ref))
    with pytest.raises(ValidationError, match="differs"):
        spot_check(ref)({"w": np.arange(6, dtype=np.float32) + 1})


@pytest.mark.parametrize("place", ["device", "host"])
def test_validate_finite_screens_bfloat16_leaves(place):
    x = np.ones((4, 8), jnp.bfloat16)
    x[2, 5] = np.nan
    leaf = jnp.asarray(x) if place == "device" else x
    out = {"logits": jnp.ones((2, 3), jnp.float32),
           "pools": {"blocks": [jnp.zeros((5,), jnp.bfloat16), leaf]}}
    with pytest.raises(ValidationError,
                       match=r"decode: 1 non-finite values in leaf "
                             r"pools/blocks/1 \(shape \(4, 8\)\)"):
        validate_finite(out, "decode")
    x[2, 5] = np.inf                       # inf is caught as NaN is
    out["pools"]["blocks"][1] = jnp.asarray(x) if place == "device" else x
    with pytest.raises(ValidationError, match="pools/blocks/1"):
        validate_finite(out)


def test_validate_finite_names_the_first_offending_leaf():
    bad = jnp.array([np.nan, 1.0, np.inf], jnp.float32)
    out = [np.arange(3), bad, np.array([np.nan]), jnp.asarray(bad)]
    with pytest.raises(ValidationError,
                       match=r"2 non-finite values in leaf 1 "):
        validate_finite(out)


def test_validate_finite_copies_only_the_verdict():
    out = {"a": jnp.ones((64, 64), jnp.bfloat16),
           "b": jnp.ones((8,), jnp.float32),
           "c": jnp.arange(6, dtype=jnp.int32),     # not screened
           "d": np.ones((3,), np.float32),          # on the host
           "e": 2.5}                                # a Python scalar
    c = validate_finite(out)
    assert c == {"leaves": 5, "screened_on_device": 2,
                 "bytes_screened": 64 * 64 * 2 + 8 * 4 + 3 * 4 + 8,
                 "bytes_to_host": 2}       # one bool flag a device leaf
    assert validate_finite({"i": np.arange(4)}) == {
        "leaves": 1, "screened_on_device": 0, "bytes_screened": 0,
        "bytes_to_host": 0}


def test_validate_finite_across_device_sets():
    """Leaves on different devices, and sharded over a mesh, are each
    screened where they live: one program per set of devices."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.runtime.guard import ValidationError, validate_finite
        d = jax.devices()
        mesh = Mesh(np.array(d[:4]), ("x",))
        sharded = jax.device_put(jnp.ones((8, 4), jnp.bfloat16),
                                 NamedSharding(mesh, P("x")))
        out = {"s": sharded,
               "a": jax.device_put(jnp.ones(3), d[0]),
               "b": jax.device_put(jnp.ones(3), d[1])}
        c = validate_finite(out)
        assert c["screened_on_device"] == 3, c
        assert c["bytes_to_host"] == 3, c
        bad = sharded.at[7, 3].set(jnp.nan)
        try:
            validate_finite({**out, "s": bad}, "sharded")
        except ValidationError as e:
            assert "1 non-finite values in leaf s " in str(e), e
        else:
            raise AssertionError("the sharded NaN was not caught")
        print("ok")
    """)
    assert out.strip().endswith("ok")


def test_paged_server_guard_screens_the_bfloat16_pool():
    """A NaN in a page no request holds leaves the logits finite; the
    guard still refuses the decode step's bf16 pool, retries, and walks
    the ladder (the poison stays in the input pool, so nothing
    recovers)."""
    from repro.configs import get_config
    from repro.launch.serve import PagedServeConfig, PagedServer
    from repro.models import init
    cfg = get_config("quickstart", smoke=True).replace(
        attn_decode_kernel="blockspace", dtype="bfloat16",
        param_dtype="bfloat16")
    srv = PagedServer(cfg, init(jax.random.PRNGKey(0), cfg),
                      PagedServeConfig(max_len=32, num_slots=2, page_size=8,
                                       num_pages=16, retries=1,
                                       backoff_base_s=0.0))
    srv.submit(0, np.arange(7) % cfg.vocab_size, 4)
    assert srv._admit_one()
    free = srv.alloc._free[0]
    assert all(free not in r.pages for r in srv.slots if r is not None)
    leaves, tree = jax.tree_util.tree_flatten(srv.pools)
    assert leaves[0].dtype == jnp.bfloat16
    pool = leaves[0]                  # ([groups,] pages, 2*Hkv, ps, d)
    leaves[0] = pool.at[(0,) * (pool.ndim - 4) + (free, 0, 0, 0)].set(
        jnp.nan)
    srv.pools = jax.tree_util.tree_unflatten(tree, leaves)
    with pytest.raises(GuardExhausted, match="non-finite"):
        srv.step()
    bad = [e for e in srv.events
           if getattr(e, "kind", None) == "validation"]
    assert bad and all(e.name == "serve.decode" for e in bad)
    assert "1 non-finite values in leaf 1/" in bad[0].error
    assert len(bad) == 2 * len(srv.ladder.rungs)   # 1 + retries per rung
    assert [e["kind"] for e in srv.events if isinstance(e, dict)] == \
        ["admit", "degrade"]


# ---------------------------------------------------------------------------
# GuardedCall
# ---------------------------------------------------------------------------

def _no_backoff():
    return Backoff(base_s=0.0, jitter=0.0)


def test_guarded_call_retries_transient_then_recovers():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise TransientFault("injected")
        return jnp.asarray(42.0)

    g = GuardedCall(flaky, "step", retries=3, backoff=_no_backoff())
    assert float(g()) == 42.0
    assert calls["n"] == 3
    assert g.recoveries == 1
    kinds = [e.kind for e in g.events]
    assert kinds == ["transient", "retry", "transient", "retry", "ok"]


def test_guarded_call_fatal_raises_immediately_with_report(tmp_path):
    calls = {"n": 0}

    def bad():
        calls["n"] += 1
        raise ValueError("shape mismatch (8,) vs (4,)")

    g = GuardedCall(bad, "decode", retries=5, backoff=_no_backoff())
    with pytest.raises(GuardExhausted) as ei:
        g()
    assert calls["n"] == 1                # fatal => no retry
    report = ei.value.report
    assert report.classification == "fatal"
    assert report.error_type == "ValueError"
    path = report.write(str(tmp_path / "r.json"))
    loaded = json.load(open(path))
    assert loaded["name"] == "decode"
    assert loaded["events"][0]["kind"] == "fatal"


def test_guarded_call_exhaustion_report():
    def always():
        raise TransientFault("still down")

    g = GuardedCall(always, "step", retries=2, backoff=_no_backoff())
    with pytest.raises(GuardExhausted) as ei:
        g()
    assert ei.value.report.classification == "exhausted"
    assert ei.value.report.attempts == 3  # 1 initial + 2 retries


def test_guarded_call_validation_failure_retries():
    calls = {"n": 0}

    def nan_once():
        calls["n"] += 1
        return jnp.asarray(np.nan if calls["n"] == 1 else 1.0)

    fixed = []
    g = GuardedCall(nan_once, "step", retries=2, backoff=_no_backoff(),
                    validators=[validate_finite],
                    before_retry=lambda: fixed.append(True))
    assert float(g()) == 1.0
    assert fixed == [True]                # before_retry hook ran
    assert [e.kind for e in g.events][0] == "validation"


def test_guarded_call_deadline_recorded_and_enforced():
    g = GuardedCall(lambda: 1, "slow", retries=0, deadline_s=-1.0,
                    backoff=_no_backoff())
    assert g() == 1                       # recorded, not enforced
    assert any(e.kind == "deadline" for e in g.events)
    g2 = GuardedCall(lambda: 1, "slow", retries=0, deadline_s=-1.0,
                     enforce_deadline=True, backoff=_no_backoff())
    with pytest.raises(GuardExhausted):
        g2()


# ---------------------------------------------------------------------------
# FaultPlan / ladder / sampling keys
# ---------------------------------------------------------------------------

def test_fault_plan_seeded_replayable_and_json_roundtrip():
    p1 = FaultPlan.from_seed(11, sites=("a", "b"), n_faults=4, horizon=9)
    p2 = FaultPlan.from_seed(11, sites=("a", "b"), n_faults=4, horizon=9)
    assert p1.to_json() == p2.to_json()
    p3 = FaultPlan.from_json(p1.to_json())
    assert p3.to_json() == p1.to_json()
    assert FaultPlan.from_seed(12, sites=("a", "b"), n_faults=4,
                               horizon=9).to_json() != p1.to_json()
    plan = FaultPlan(0, [FaultSpec("transient_error", "s", 2, rung=0)])
    assert plan.for_call("s", 2, rung=0)
    assert not plan.for_call("s", 2, rung=1)   # rung-conditioned
    assert plan.for_call("s", 2, rung=None)    # unconditioned caller
    assert not plan.for_call("s", 3, rung=0)
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultSpec("meteor_strike", "s", 0)


def test_degradation_ladder_transitions():
    seen = []
    lad = DegradationLadder([{"decode": "blockspace"}, {"decode": "xla"},
                             {"decode": "cpu"}], on_transition=seen.append)
    assert lad.current() == {"decode": "blockspace"}
    assert not lad.degraded
    assert lad.step_down("nan storm")
    assert lad.level == 1 and lad.degraded
    assert lad.step_down("still failing")
    assert lad.exhausted()
    assert not lad.step_down("bottom")     # nothing left
    assert len(lad.transitions) == 2 == len(seen)
    assert lad.transitions[0]["reason"] == "nan storm"
    assert lad.transitions[0]["to"] == {"decode": "xla"}


def test_sample_key_pure_function_of_coordinates():
    base = jax.random.PRNGKey(3)
    k1 = sample_key(base, pos=7, batch=4)
    k2 = sample_key(base, pos=7, batch=4)
    assert np.array_equal(np.asarray(k1), np.asarray(k2))
    assert k1.shape[0] == 4
    assert not np.array_equal(np.asarray(k1),
                              np.asarray(sample_key(base, 8, 4)))
    # distinct per slot
    assert len({tuple(np.asarray(r)) for r in k1}) == 4


# ---------------------------------------------------------------------------
# fault_tolerance surfaces (satellite: Heartbeat / PreemptionGuard /
# retry_step)
# ---------------------------------------------------------------------------

def test_heartbeat_straggle_callback_fires():
    from repro.distributed.fault_tolerance import Heartbeat
    seen = []
    hb = Heartbeat(deadline_s=0.0, on_straggle=seen.append)
    dt = hb.beat()
    assert hb.straggle_events == 1
    assert seen and seen[0] == dt
    hb2 = Heartbeat(deadline_s=1e6)
    hb2.beat()
    assert hb2.straggle_events == 0


def test_preemption_guard_install_restore_and_fire():
    from repro.distributed.fault_tolerance import PreemptionGuard
    before = signal.getsignal(signal.SIGTERM)
    with PreemptionGuard() as g:
        assert signal.getsignal(signal.SIGTERM) != before
        assert not g.fired
        os.kill(os.getpid(), signal.SIGTERM)
        assert g.fired
    assert signal.getsignal(signal.SIGTERM) == before


def test_retry_step_classifies_transient_vs_fatal():
    from repro.distributed.fault_tolerance import retry_step
    calls = {"n": 0}
    slept = []

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("UNAVAILABLE: preempted")
        return "ok"

    assert retry_step(flaky, retries=3, backoff_s=0.25,
                      sleep=slept.append) == "ok"
    assert calls["n"] == 3
    assert len(slept) == 2
    assert all(s > 0 for s in slept)      # jittered backoff slept twice

    def fatal():
        calls["n"] += 1
        raise ValueError("bad shape")

    calls["n"] = 0
    with pytest.raises(ValueError):
        retry_step(fatal, retries=5, sleep=slept.append)
    assert calls["n"] == 1                # fatal => no retry


def test_retry_step_exhaustion_reraises():
    from repro.distributed.fault_tolerance import retry_step
    calls = {"n": 0}

    def down():
        calls["n"] += 1
        raise TransientFault("net down")

    with pytest.raises(TransientFault):
        retry_step(down, retries=2, sleep=lambda s: None)
    assert calls["n"] == 3


# ---------------------------------------------------------------------------
# checkpoint torn-write recovery (satellite)
# ---------------------------------------------------------------------------

def test_checkpoint_torn_write_recovery(tmp_path):
    from repro.checkpoint.manager import CheckpointManager
    mgr = CheckpointManager(str(tmp_path), keep=3)
    p1 = {"w": np.arange(8, dtype=np.float32)}
    p2 = {"w": np.arange(8, dtype=np.float32) * 2}
    mgr.save(1, p1)
    mgr.save(2, p2)
    tear_checkpoint(str(tmp_path))
    # auto-select falls back past the torn latest step
    step, params, _, meta = mgr.restore(None, {"w": np.zeros(8,
                                                            np.float32)})
    assert step == 1
    assert np.array_equal(np.asarray(params["w"]), p1["w"])
    assert meta["skipped_torn_steps"] == [2]
    # an explicitly requested torn step is never silently substituted
    with pytest.raises(Exception):
        mgr.restore(2, {"w": np.zeros(8, np.float32)})
    # the next save clears the torn .tmp debris
    mgr.save(3, p2)
    assert not [n for n in os.listdir(tmp_path) if n.endswith(".tmp")]
    step, params, _, meta = mgr.restore(None, {"w": np.zeros(8,
                                                             np.float32)})
    assert step == 3 and "skipped_torn_steps" not in meta


def test_checkpoint_all_torn_raises(tmp_path):
    from repro.checkpoint.manager import CheckpointManager
    mgr = CheckpointManager(str(tmp_path), keep=3)
    mgr.save(1, {"w": np.zeros(4, np.float32)})
    tear_checkpoint(str(tmp_path))
    with pytest.raises(FileNotFoundError, match="torn"):
        mgr.restore(None, {"w": np.zeros(4, np.float32)})


def test_tune_cache_rejects_corrupt_entry(tmp_path, monkeypatch):
    from repro.core import tune
    path = str(tmp_path / "tune.json")
    monkeypatch.setenv(tune.CACHE_ENV, path)
    params = {"fractal": "sierpinski-gasket", "n": 16, "block": 4,
              "rule": "parity"}
    corrupt_tune_cache(path, "ca", params)
    assert tune.best("ca", params, default={"lowering": "closed_form"}) \
        == {"lowering": "closed_form"}
    # a sane entry still round-trips
    cache = tune.TuneCache(path)
    cache.put("ca", tune._with_backend(dict(params)),
              {"lowering": "prefetch_lut", "fuse": 2, "coarsen": 1}, 9.0)
    assert tune.best("ca", params, cache=cache)["fuse"] == 2


# ---------------------------------------------------------------------------
# chaos: Pallas-layer scenarios (poisoned tile, corrupt table)
# ---------------------------------------------------------------------------

def test_chaos_poison_tile_detected_and_recovered():
    from repro.runtime.chaos import scenario_poison_tile
    r = scenario_poison_tile(0, True)
    assert r["status"] == "recovered", r


def test_chaos_corrupt_table_detected_and_recovered():
    from repro.runtime.chaos import scenario_corrupt_table
    r = scenario_corrupt_table(0, True)
    assert r["status"] == "recovered", r


def test_chaos_bitflip_poison_survives_nan_screen_caught_by_spot_check():
    """A finite bit-flip sails through the NaN screen -- only the
    spot-check validator catches it (why the ladder keeps both)."""
    from repro.kernels.sierpinski_write import sierpinski_write
    m = jnp.zeros((16, 16), jnp.float32)

    def run():
        return sierpinski_write(m, 1.0, block=4, grid_mode="closed_form",
                                coarsen=1, num_stages=1)

    clean = np.asarray(run())
    plan = FaultPlan(0, [FaultSpec("poison_tile", "pallas", 0,
                                   mode="bitflip")])
    with ChaosInjector(plan) as chaos:
        bad = np.asarray(run())            # unguarded: corruption lands
        assert not np.array_equal(bad, clean)
        validate_finite(bad)               # NaN screen is blind to it
        with pytest.raises(ValidationError):
            spot_check(clean)(bad)
        chaos.refresh()
        guard = GuardedCall(run, "write", retries=2,
                            backoff=_no_backoff(),
                            validators=[spot_check(clean)],
                            before_retry=chaos.refresh)
        out = np.asarray(guard())
    assert np.array_equal(out, clean)


def test_chaos_injector_restores_hooks():
    from repro.core import backend as backend_lib
    orig_pp = jax.lax.ppermute
    plan = FaultPlan(0, [FaultSpec("drop_halo", "ppermute", 0)])
    with ChaosInjector(plan):
        assert jax.lax.ppermute is not orig_pp
    assert jax.lax.ppermute is orig_pp
    prev = backend_lib.set_emit_hook(None)   # nothing left installed
    backend_lib.set_emit_hook(prev)
    assert prev is None


# ---------------------------------------------------------------------------
# serving: EOS, deterministic sampling, ladder, drain/resume
# ---------------------------------------------------------------------------

def _server(scfg=None, chaos=None, decode_kernel=""):
    from repro.configs import get_config
    from repro.launch.serve import ServeConfig, Server
    from repro.models import init
    cfg = get_config("quickstart", smoke=True)
    if decode_kernel:
        cfg = cfg.replace(attn_decode_kernel=decode_kernel)
    params = init(jax.random.PRNGKey(0), cfg)
    scfg = scfg or ServeConfig(max_len=16, retries=3,
                               backoff_base_s=0.0)
    return cfg, params, Server(cfg, params, scfg, chaos=chaos)


def test_server_eos_early_stop_per_slot():
    from repro.launch.serve import ServeConfig
    cfg, params, server = _server(ServeConfig(max_len=16,
                                              backoff_base_s=0.0))
    prompts = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 4))
    ref = server.generate(prompts, max_new=8)
    assert ref.shape == (2, 8)             # eos_id=-1: never stops
    # pick the token slot 0 greedily emits at step 2 as the EOS id
    eos = int(ref[0, 2])
    _, _, server2 = _server(ServeConfig(max_len=16, eos_id=eos,
                                        backoff_base_s=0.0))
    out = server2.generate(prompts, max_new=8)
    # slot 0 finished at step 2: everything after is EOS padding
    assert out[0, 2] == eos
    assert (out[0, 3:] == eos).all()
    # unfinished slots keep generating the reference stream
    for b in range(2):
        stop = np.argmax(ref[b] == eos) if (ref[b] == eos).any() \
            else ref.shape[1]
        assert np.array_equal(out[b, :stop + 1], ref[b, :stop + 1])
    # all slots finished => the loop stops early
    if (out == eos).all(axis=1).all():
        assert out.shape[1] < 8


def test_server_transient_faults_recover_bit_identical():
    from repro.runtime.chaos import scenario_transient_runtime
    r = scenario_transient_runtime(0, True)
    assert r["status"] == "recovered", r
    assert r["detected"] and r["bit_identical"]


def test_server_degradation_ladder_blockspace_to_xla():
    from repro.launch.serve import ServeConfig, Server
    scfg = ServeConfig(max_len=16, temperature=0.5, seed=9, retries=2,
                       backoff_base_s=0.0)
    cfg, params, ref_xla = _server(scfg, decode_kernel="xla")
    prompts = np.random.default_rng(3).integers(0, cfg.vocab_size, (2, 4))
    want = ref_xla.generate(prompts, max_new=5)

    # every rung-0 decode attempt faults (indices cover the retry
    # budget); the guard exhausts, the ladder steps down to xla, and
    # the stream completes there
    plan = FaultPlan(0, [FaultSpec("transient_error", "serve.decode", i,
                                   rung=0) for i in range(3)])
    chaos = ChaosInjector(plan)
    cfg_bs = cfg.replace(attn_decode_kernel="blockspace")
    faulty = Server(cfg_bs, params, scfg, chaos=chaos)
    assert faulty.ladder.rungs[0]["decode_kernel"] == "blockspace"
    out = faulty.generate(prompts, max_new=5)

    assert faulty.state == ServerState.DEGRADED
    assert faulty.ladder.level == 1
    assert len(faulty.ladder.transitions) == 1
    t = faulty.ladder.transitions[0]
    assert t["from"]["decode_kernel"] == "blockspace"
    assert t["to"]["decode_kernel"] == "xla"
    assert np.array_equal(out, want)       # served stream == xla run
    assert any(e["kind"] == "degrade" for e in faulty.events
               if isinstance(e, dict))


def test_server_compile_refusal_is_fatal_not_degraded(tmp_path):
    """A decode step the compiler refuses (here: an HBM overrun) must
    end the run with a fatal report, not serve on from the xla rung."""
    from jax.errors import JaxRuntimeError
    from repro.launch.serve import ServeConfig, Server
    scfg = ServeConfig(max_len=16, retries=3, backoff_base_s=0.0,
                       report_dir=str(tmp_path))
    cfg, params, _ = _server(scfg)
    server = Server(cfg.replace(attn_decode_kernel="blockspace"), params,
                    scfg)
    assert len(server.ladder.rungs) > 1
    calls = []

    def refused(*args):
        calls.append(1)
        raise JaxRuntimeError(
            "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out "
            "of memory in memory space hbm. Used 28.55G of 15.75G hbm.")

    server._decode_fn = refused
    prompts = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 4))
    with pytest.raises(GuardExhausted):
        server.generate(prompts, max_new=4)
    assert len(calls) == 1                 # no retry
    assert server.ladder.level == 0 and not server.ladder.transitions
    assert server.state == ServerState.HEALTHY
    rep = json.load(open(tmp_path / "failure_serve_decode.json"))
    assert rep["classification"] == "fatal"


def test_server_ladder_exhausted_writes_failure_report(tmp_path):
    from repro.launch.serve import ServeConfig, Server
    from repro.configs import get_config
    from repro.models import init
    cfg = get_config("quickstart", smoke=True)   # xla: single-rung ladder
    params = init(jax.random.PRNGKey(0), cfg)
    scfg = ServeConfig(max_len=16, retries=1, backoff_base_s=0.0,
                       report_dir=str(tmp_path))
    plan = FaultPlan(0, [FaultSpec("transient_error", "serve.decode", i)
                         for i in range(4)])
    server = Server(cfg, params, scfg, chaos=ChaosInjector(plan))
    prompts = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 4))
    with pytest.raises(GuardExhausted):
        server.generate(prompts, max_new=4)
    reports = [f for f in os.listdir(tmp_path) if f.endswith(".json")]
    assert reports, "no failure report written"
    rep = json.load(open(tmp_path / reports[0]))
    assert rep["classification"] == "exhausted"
    assert rep["name"] == "serve.decode"


def test_server_sigterm_drain_and_resume_bit_identical():
    from repro.runtime.chaos import scenario_sigterm_mid_decode
    r = scenario_sigterm_mid_decode(0, True)
    assert r["status"] == "recovered", r
    assert r["drained"] and r["bit_identical"]


# ---------------------------------------------------------------------------
# trainer wiring + chaos CLI
# ---------------------------------------------------------------------------

def test_trainer_writes_failure_report_on_fatal_step(tmp_path):
    from repro.configs import get_config
    from repro.data.pipeline import DataConfig, SyntheticPipeline
    from repro.launch.train import TrainConfig, Trainer
    cfg = get_config("quickstart", smoke=True)
    tcfg = TrainConfig(steps=2, log_every=100, ckpt_dir=str(tmp_path),
                       step_retries=1, retry_backoff_s=0.0)
    tr = Trainer(cfg, tcfg)
    tr._step = lambda p, o, b: (_ for _ in ()).throw(
        ValueError("injected fatal shape error"))
    pipe = SyntheticPipeline(DataConfig(vocab_size=cfg.vocab_size,
                                        seq_len=16, global_batch=2))
    with pytest.raises(ValueError):
        tr.run(pipe)
    reports = [f for f in os.listdir(tmp_path)
               if f.startswith("failure_step_")]
    assert reports
    rep = json.load(open(tmp_path / reports[0]))
    assert rep["classification"] == "fatal"


def test_chaos_matrix_cli_multi_device():
    out = run_sub("""
        from repro.runtime.chaos import main
        rc = main(["--matrix", "--smoke", "--quiet",
                   "--only", "poison_tile,drop_halo,fatal_report",
                   "--out", "/tmp/chaos_ci_report.json"])
        import json
        rep = json.load(open("/tmp/chaos_ci_report.json"))
        assert rep["ok"], rep
        assert rep["devices"] == 4
        statuses = {r["fault"]: r["status"] for r in rep["results"]}
        assert statuses["drop_halo"] == "recovered", statuses
        print("RC", rc)
    """)
    assert "RC 0" in out
