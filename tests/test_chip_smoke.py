"""The chip smoke's phases, rehearsed on the CPU at tiny sizes under the
Pallas interpreter (the script's own device check refuses the CPU)."""
import importlib.util
import os
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPT = os.path.join(_ROOT, "chip_smoke.py")


def _smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(args, env_extra=None, code=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(_ROOT, "src"))
    env.update(env_extra or {})
    cmd = [sys.executable] + (["-c", textwrap.dedent(code)] if code
                              else [_SCRIPT] + args)
    return subprocess.run(cmd, capture_output=True, text=True, env=env,
                          cwd=_ROOT, timeout=900)


def test_smoke_refuses_the_cpu():
    r = _run([])
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


def test_smoke_kernel_phase_tiny(chip_semantics):
    _smoke().phase_kernels(n_embed=64, n_ca=32, n_compact=128, block=8,
                           ca_steps=5, compact_steps=4, fuse=2)


def test_smoke_serve_phase_tiny(chip_semantics):
    from repro.configs import get_config
    _smoke().phase_serve(get_config("phi3-mini-3.8b", smoke=True),
                         num_slots=2, page_size=8, prompt_lens=(8, 20),
                         n_requests=3, max_new=4)


def test_smoke_logit_check_catches_a_wrong_position(monkeypatch):
    """The decode-vs-forward comparison must fail when the decode step
    reads the wrong cache position."""
    smoke = _smoke()
    from repro.configs import get_config
    from repro.models import model as model_lib
    real = model_lib.decode_step_paged

    def off_by_one(params, inputs, pools, table, pos, active, cfg):
        pos = pos - active.astype(pos.dtype)
        return real(params, inputs, pools, table, pos, active, cfg)

    monkeypatch.setattr(model_lib, "decode_step_paged", off_by_one)
    with pytest.raises(AssertionError, match="decode logits"):
        smoke.phase_serve(get_config("phi3-mini-3.8b", smoke=True),
                          num_slots=2, page_size=8, prompt_lens=(8,),
                          n_requests=1, max_new=3)


def test_smoke_four_chip_phase_on_virtual_devices():
    r = _run([], env_extra={
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"},
        code=f"""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {_SCRIPT!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        smoke.phase_four_chips(n=128, window=32, block=8, steps=4,
                               fuse=2)
        print("OK")
        """)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "OK" in r.stdout


def test_smoke_stream_check_catches_a_wrong_server_position(monkeypatch):
    """The served-stream comparison must fail when only the server's own
    batched decode reads and writes the wrong cache position (the
    single-step check, which builds its own page table, still passes)."""
    smoke = _smoke()
    from repro.configs import get_config
    from repro.launch.serve import PagedServer
    real = PagedServer._decode_step

    def off_by_one(self, toks, table, posv, act):
        return real(self, toks, table, posv - act.astype(posv.dtype), act)

    monkeypatch.setattr(PagedServer, "_decode_step", off_by_one)
    with pytest.raises(AssertionError, match="served token"):
        smoke.phase_serve(get_config("phi3-mini-3.8b", smoke=True),
                          num_slots=2, page_size=8, prompt_lens=(8, 20),
                          n_requests=3, max_new=4)
