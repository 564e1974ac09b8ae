"""The benchmark's cells at sizes the CPU runs in seconds: the sizes are
steered here, in the test, and the rest of a run is the harness's own
(its look for a chip is skipped: the CPU stands in for the device)."""
import time

import jax

from bench import harness

#: the CPU has no entry in bench/peaks.json; any positive peaks do
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}

GASKET = {"n": 256, "block": 32}
PHI3 = {"hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 4, "intermediate_size": 128,
        "num_hidden_layers": 2, "vocab_size": 512}
SERVE = {"clients": 4, "prompt_lens": [8, 16], "output_lens": [3, 6],
         "max_len": 32, "page_size": 4, "num_pages": 1 + 4 * 8,
         "check_requests": 4}


def tiny(name, config=None, **traffic):
    """(workload, config) of cell ``name`` cut to a CPU size; ``config``
    and ``traffic`` override further."""
    w, c = harness.load_cell(name)
    tr = dict(w["traffic"])
    if w["driver"].startswith("gasket"):
        c = dict(c, **GASKET)
        if "steps_per_call" in tr:
            tr.update(steps_per_call=16)
    else:
        c = dict(c, **PHI3)
        tr.update(SERVE)
    tr.update(traffic)
    return dict(w, traffic=tr), dict(c, **(config or {}))


def run(name, *, seed=2 ** 31 + 11, seconds=0.3, trace=0, control=0,
        config=None, **traffic):
    w, c = tiny(name, config, **traffic)
    return harness.run_cell(w, c, seed=seed, seconds=seconds, trace=trace,
                            t_start=time.perf_counter(),
                            devices=jax.devices(), peaks=PEAKS,
                            control=control)
