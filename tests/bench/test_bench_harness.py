"""The harness: it refuses to run without a listed accelerator, it finds
cells, configurations and per-layer metrics by name, and the counts it
divides by the chip's peaks are the hand-counted ones."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from bench import counts, harness

ROOT = harness.ROOT


def _env():
    return dict(os.environ, JAX_PLATFORMS="cpu",
                PYTHONPATH=os.path.join(ROOT, "src"))


def test_refuses_the_cpu_and_prints_no_result():
    r = subprocess.run([sys.executable, "-m", "bench.run", "--workload",
                        "gasket.map-write", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], capture_output=True, text=True,
                       env=_env(), cwd=ROOT, timeout=300)
    assert r.returncode == 3
    assert r.stdout.strip() == ""
    assert "no accelerator" in r.stderr


def test_a_device_not_in_the_peaks_table_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.DeviceError):
        harness.peaks_for("TPU v4")


def test_every_cell_of_the_benchmark_has_its_files():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    readers = harness.metric_readers()
    for cell in bench["workloads"]:
        w, c = harness.load_cell(cell["name"])
        assert (w["config"], w["chips"], w["why"]) == \
            (cell["config"], cell["chips"], cell["why"])
        harness.driver_module(w["driver"])
    for m in bench["per_layer"]:
        assert readers[m["name"]].UNIT == m["unit"]
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))


def test_new_cell_config_and_metric_are_files_alone(tmp_path):
    """In a copy of the benchmark, a cell, a configuration and a per-layer
    metric are added as new files; the harness runs the new cell and
    reports the new metric with no other file edited."""
    copy = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), copy / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: open(p, "rb").read() for p in
              map(str, (copy / "bench").rglob("*")) if os.path.isfile(p)}
    cfg = json.load(open(copy / "bench/configs/gasket-compact.json"))
    cfg.update(name="gasket-small", n=128, block=32)
    (copy / "bench/configs/gasket-small.json").write_text(json.dumps(cfg))
    w = json.load(open(copy / "bench/workloads/gasket.map-write.json"))
    w.update(name="gasket.small-write", config="gasket-small")
    (copy / "bench/workloads/gasket.small-write.json").write_text(
        json.dumps(w))
    (copy / "bench/metrics/calls.gasket.py").write_text(textwrap.dedent('''
        """Bytes the map kernel wrote in the traced window."""
        UNIT = "B"


        def read(ctx):
            k = ctx.work.get("kernel")
            return k["bytes"] if k else None
        '''))
    code = textwrap.dedent('''
        import json, time, jax
        from bench import harness
        w, c = harness.load_cell("gasket.small-write")
        line = harness.run_cell(w, c, seed=5, seconds=0.2, trace=1,
                                t_start=time.perf_counter(),
                                devices=jax.devices(),
                                peaks={"bf16_flops_per_s": 1.0,
                                       "hbm_bytes_per_s": 1.0})
        print(json.dumps(line))
        ''')
    env = _env()
    env["PYTHONPATH"] = f"{copy}:{env['PYTHONPATH']}"
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=copy, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"]
    assert line["metrics"]["calls.gasket"]["value"] > 0
    for p, data in before.items():
        assert open(p, "rb").read() == data, p


def test_counts_by_hand():
    assert counts.gasket_members(8) == 27
    assert counts.ca_fused(n=8, steps=2, stored_bytes=100) == \
        {"ops": 7 * 27 * 2, "bytes": 200}
    assert counts.write(stored_bytes=100) == {"ops": 0, "bytes": 100}
    # 2 slots with 3 and 5 live keys, 2 layers, 4 query / 2 KV heads of 8
    assert counts.paged_decode(contexts=[3, 5], layers=2, heads=4,
                               kv_heads=2, head_dim=8) == \
        {"ops": 2 * 4 * 8 * 8 * 4, "bytes": 2 * 2 * 8 * 8 * 2 * 2}
    dims = dict(layers=1, d_model=4, heads=2, kv_heads=1, head_dim=2,
                d_ff=8, vocab=10)
    # weights 4*2*(2*2+2*1) = 48 and 3*4*8 = 96, two FLOPs each; three
    # keys at 4*2*2 per key; the head 2*4*10
    assert counts.model_flops_per_token(context=3, **dims) == \
        2 * (48 + 96) + 3 * 16 + 80
    # two prompt tokens attend over 1 and 2 keys; one head
    assert counts.prefill_flops(tokens=2, **dims) == 2 * 288 + 80 + 3 * 16


def test_roofline_share_takes_the_binding_bound():
    peaks = harness.peaks_for("TPU v5 lite")
    ops, nbytes = 197e12 * 1e-3, 819e9 * 2e-3        # 1 ms and 2 ms
    assert counts.roofline_share(ops=ops, nbytes=nbytes, seconds=4e-3,
                                 peaks=peaks) == pytest.approx(50.0)
    assert counts.roofline_share(ops=ops, nbytes=0, seconds=4e-3,
                                 peaks=peaks) == pytest.approx(25.0)
    assert counts.roofline_share(ops=ops, nbytes=nbytes, seconds=0,
                                 peaks=peaks) is None
