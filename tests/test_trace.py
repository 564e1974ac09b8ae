"""The program's spans (``repro.runtime.trace``) as the profiler records
them: names, nesting, counters, and what the benchmark's serving
metrics read from them; the kernels' names in the lowered TPU program;
and the spans with no profiler running."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import TraceAnnotation

from bench import trace as trace_lib
from repro.runtime import trace as program_trace
from repro.runtime.trace import span

#: the benchmark's own spans: no program span may take one of these names
BENCH_SPANS = {"bench.window", "ops.ca_run", "ops.sierpinski_write",
               "PagedServer.step", "PagedServer._admit_one"}
STEP_CHILDREN = ("serve.grow", "serve.inputs", "guard.call",
                 "serve.release", "serve.sample", "serve.table")
ADMIT_CHILDREN = ("guard.call", "serve.scatter", "serve.sample",
                  "serve.table")


def _traced(trace_dir, fn):
    """Run ``fn`` under the profiler, Python tracer off (as the
    benchmark records), and return its result."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        return fn()
    finally:
        jax.profiler.stop_trace()


def _spans(trace_dir):
    """The host spans of the program, every counter kept:
    [(name, start_ns, end_ns, {counter: value})], in start order."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.split(".")[0] in ("serve", "guard", "kernels"):
                    start = int(e.start_ns)
                    out.append((e.name, start, start + int(e.duration_ns),
                                dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _inside(spans, parent, names):
    return [s for s in spans if s[0] in names
            and parent[1] <= s[1] and s[2] <= parent[2] and s is not parent]


def _documented(name):
    return f"``{name}``" in program_trace.__doc__


@pytest.fixture(scope="module")
def served_trace(tmp_path_factory):
    from repro.configs import get_config
    from repro.launch.serve import PagedServeConfig, PagedServer
    from repro.models import init
    cfg = get_config("quickstart", smoke=True).replace(
        attn_decode_kernel="xla")
    params = init(jax.random.PRNGKey(0), cfg)
    srv = PagedServer(cfg, params, PagedServeConfig(
        max_len=32, temperature=0.0, num_slots=2, page_size=4,
        num_pages=16))
    rng = np.random.default_rng(3)
    reqs = [rng.integers(0, cfg.vocab_size, (n,)) for n in (7, 12, 5)]
    srv.run(reqs[:1], max_new=2)          # compiles outside the trace
    srv.done.clear()
    d = tmp_path_factory.mktemp("served")
    _traced(d, lambda: srv.run(reqs, max_new=5))
    return d, _spans(d), srv


def test_serving_spans_nest_and_cover_each_step(served_trace):
    _, spans, srv = served_trace
    names = {s[0] for s in spans}
    want = {"serve.step", "serve.admit", "serve.scatter", "guard.run",
            "guard.validate", *STEP_CHILDREN}
    assert want <= names, want - names
    steps = [s for s in spans if s[0] == "serve.step"]
    assert len(steps) >= 4
    self_ms = []
    for st in steps:
        kids = _inside(spans, st, STEP_CHILDREN)
        assert sorted(k[0] for k in kids) == sorted(STEP_CHILDREN), st
        call, = (k for k in kids if k[0] == "guard.call")
        assert call[3]["site"] == "serve.decode"
        run = _inside(spans, call, {"guard.run"})
        assert [r[3]["attempt"] for r in run] == [1]
        assert len(_inside(spans, call, {"guard.validate"})) == 1
        self_ms.append(1e-6 * ((st[2] - st[1])
                               - sum(k[2] - k[1] for k in kids)))
        assert {"step", "active", "pages_in_use", "preempted"} \
            <= set(st[3]), st[3]
        # the xla decode rung runs no paged kernel grid
        assert st[3]["decode_grid_steps"] == srv.decode_grid_steps == 0
    first = srv.steps_served - len(steps)     # the warm-up's steps
    assert [st[3]["step"] for st in steps] == \
        list(range(first, srv.steps_served))
    assert sum(self_ms) / len(self_ms) < 2.0
    admits = [s for s in spans if s[0] == "serve.admit"]
    assert len(admits) == 3
    for ad in admits:
        kids = _inside(spans, ad, ADMIT_CHILDREN)
        assert sorted(k[0] for k in kids) == sorted(ADMIT_CHILDREN), ad
        call, = (k for k in kids if k[0] == "guard.call")
        assert call[3]["site"] == "serve.prefill"
        assert {"rid", "prompt_tokens", "pages", "replayed"} <= set(ad[3])


def test_guard_validate_counts_the_host_copy(served_trace):
    _, spans, srv = served_trace
    pools = sum(x.nbytes for x in jax.tree_util.tree_leaves(srv.pools))
    decode = [v for s in spans if s[0] == "serve.step"
              for c in _inside(spans, s, {"guard.call"})
              for v in _inside(spans, c, {"guard.validate"})]
    assert decode
    logits = srv.scfg.num_slots * srv.cfg.vocab_size * 4    # (B,1,V) f32
    for v in decode:
        c = v[3]
        assert c["bytes_screened"] == pools + logits
        assert c["screened_on_device"] == c["leaves"] >= 2
        assert c["bytes_to_host"] == c["leaves"]  # one flag a leaf


def test_program_spans_are_documented_and_not_the_benchmarks(served_trace):
    d, spans, _ = served_trace
    names = {s[0] for s in spans}
    assert not names & BENCH_SPANS
    assert all(_documented(n) for n in names), \
        [n for n in names if not _documented(n)]
    # as the benchmark reads them: string counters kept, on one thread
    events = trace_lib.load(str(d))
    host = {e.name for e in events if not e.plane.startswith("/device:")}
    assert not host & BENCH_SPANS
    assert any(e.name == "guard.call" and "site=serve.decode" in e.meta
               for e in events)


def test_traced_serving_cell_splits_each_step(monkeypatch):
    from tests.bench import tiny_cells
    seen = []

    def load(trace_dir):
        seen.extend(real(trace_dir))
        return list(seen)

    real = trace_lib.load
    monkeypatch.setattr(trace_lib, "load", load)
    line = tiny_cells.run("phi3.chat-decode-c8", trace=1, seconds=0.4)
    m = {k: v["value"] for k, v in line["metrics"].items()}
    parts = ("guard_ms_per_step.serve", "decode_call_ms_per_step.serve",
             "bookkeeping_ms_per_step.serve")
    for name in parts:
        assert m[name] > 0, name
        assert line["metrics"][name]["unit"] == "ms"
    summary = trace_lib.Summary(seen)
    steps = [(a, b) for a, b in summary.spans("serve.step")
             if a >= summary.t0 and b <= summary.t1]
    mean_ms = 1e-6 * sum(b - a for a, b in steps) / len(steps)
    assert sum(m[n] for n in parts) == pytest.approx(mean_ms, abs=1.0)


KERNELS = {
    "sierpinski_write": lambda ops: (
        lambda m: ops.sierpinski_write(
            m, 1.0, block=128, grid_mode="closed_form", num_stages=1,
            backend="tpu"), [(512, 512)]),
    "sierpinski_sum": lambda ops: (
        lambda m: ops.sierpinski_sum(
            m, block=128, grid_mode="closed_form", num_stages=1,
            backend="tpu"), [(512, 512)]),
    "sierpinski_ca_fused": lambda ops: (
        lambda a, b: ops.ca_run(
            a, b, 4, fuse=2, block=128, grid_mode="closed_form",
            num_stages=1, backend="tpu"), [(512, 512)] * 2),
    "flash_attention": lambda ops: (
        lambda q, k, v: ops.flash_attention(
            q, k, v, kind="causal", block_q=128, block_k=128,
            grid_mode="closed_form", backend="tpu"),
        [((1, 2, 256, 128), jnp.bfloat16)] * 3),
}


def _paged(_ops):
    from repro.kernels.flash_attention import paged_flash_attention
    return (lambda q, kv, pt, pos: paged_flash_attention(
        q, kv, pt, pos, grid_mode="closed_form", backend="tpu"),
        [((2, 2, 1, 128), jnp.bfloat16),
         ((9, 4, 16, 128), jnp.bfloat16),
         ((2, 4), jnp.int32), ((2,), jnp.int32)])


KERNELS["_paged_impl_decode"] = _paged


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_emit_name_is_the_tpu_kernel_name(name):
    import re

    from repro.kernels import ops
    fn, shapes = KERNELS[name](ops)
    args = [jax.ShapeDtypeStruct(*s) if isinstance(s[0], tuple)
            else jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    assert set(re.findall(r'kernel_name = "([^"]*)"', text)) == {name}


def test_kernel_entry_spans_and_counters(tmp_path):
    from repro.kernels import ops
    n, block = 64, 16
    m = jnp.zeros((n, n), jnp.float32)

    def work():
        w = ops.sierpinski_write(m, 1.0, block=block,
                                 grid_mode="closed_form")
        c = ops.ca_run(w, jnp.zeros_like(w), 5, fuse=2, block=block,
                       grid_mode="closed_form")
        return jax.block_until_ready((w, c))

    work()                                  # compiles outside the trace
    _traced(tmp_path, work)
    spans = _spans(tmp_path)
    for entry in ("kernels.sierpinski_write", "kernels.ca_run"):
        top, = (s for s in spans if s[0] == entry)
        kids = _inside(spans, top, {f"{entry}.schedule",
                                    f"{entry}.dispatch"})
        assert [k[0] for k in kids] == [f"{entry}.schedule",
                                        f"{entry}.dispatch"]
        assert top[3]["grid_mode"] == "closed_form"
        assert top[3]["storage"] == "embedded"
        assert top[3]["grid_steps"] == 9         # 3^2 member blocks
        assert all(_documented(s[0]) for s in [top, *kids])
    ca, = (s for s in spans if s[0] == "kernels.ca_run")
    assert (ca[3]["steps"], ca[3]["fuse"], ca[3]["launches"]) == (5, 2, 3)


def test_spans_run_with_the_profiler_off():
    from repro.kernels import ops
    from repro.runtime.guard import GuardedCall, validate_finite
    assert not TraceAnnotation.is_enabled()
    with span("serve.step", step=0, active=1) as s:
        s.set_metadata(pages_in_use=3, preempted=0)
    g = GuardedCall(lambda x: x + 1, "test.site", validators=[
        validate_finite])
    assert float(g(jnp.float32(1))) == 2.0
    assert [e.kind for e in g.events] == ["ok"]
    m = jnp.zeros((64, 64), jnp.float32)
    w = ops.sierpinski_write(m, 1.0, block=16, grid_mode="closed_form")
    assert float(jnp.sum(w)) == 3 ** 6         # the gasket's 729 cells
    c = ops.ca_run(w, jnp.zeros_like(w), 2, fuse=2, block=16,
                   grid_mode="closed_form")
    assert c.shape == w.shape


def test_validate_finite_counts_what_it_copies_and_screens():
    from repro.runtime.guard import validate_finite
    out = {"f": jnp.ones((4, 8), jnp.float32),
           "h": jnp.ones((16,), jnp.bfloat16),
           "i": np.arange(5, dtype=np.int32)}
    assert validate_finite(out) == {"leaves": 3,
                                    "bytes_to_host": 2,
                                    "bytes_screened": 128 + 32,
                                    "screened_on_device": 2}
