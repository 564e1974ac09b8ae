"""Architecture registry: the 10 assigned archs + quickstart.

Each ``<arch>.py`` exposes ``full()`` (the exact published config) and
``smoke()`` (reduced same-family config for CPU tests).  ``META`` holds
per-arch dry-run knobs: whether the arch is sub-quadratic (runs the
long_500k cell), whether expert/ffn weights need FSDP sharding to fit,
sequence-sharded activations, and train-time grad accumulation.
"""
from __future__ import annotations

import importlib
from typing import Dict, Optional

from repro.models import ModelConfig

ARCHS = [
    "falcon-mamba-7b",
    "gemma3-12b",
    "qwen1.5-32b",
    "qwen2.5-32b",
    "phi3-mini-3.8b",
    "deepseek-v2-236b",
    "llama4-maverick-400b-a17b",
    "musicgen-large",
    "zamba2-2.7b",
    "internvl2-26b",
]

_MODULES = {a: a.replace("-", "_").replace(".", "_") for a in ARCHS}
_MODULES["quickstart"] = "quickstart"
# served on one chip by the benchmark, outside the dry-run's assignment
_MODULES["deepseek-v2-lite-16b"] = "deepseek_v2_lite_16b"

# input shapes assigned to the LM-family pool (seq_len x global_batch)
SHAPES = {
    "train_4k":    {"kind": "train",   "seq": 4096,   "batch": 256},
    "prefill_32k": {"kind": "prefill", "seq": 32768,  "batch": 32},
    "decode_32k":  {"kind": "decode",  "seq": 32768,  "batch": 128},
    "long_500k":   {"kind": "decode",  "seq": 524288, "batch": 1},
}

# per-arch dry-run metadata
META: Dict[str, Dict] = {
    "falcon-mamba-7b":          {"subquadratic": True,  "fsdp": False,
                                 "seq_shard": True, "grad_accum": 4},
    "gemma3-12b":               {"subquadratic": True,  "fsdp": False,
                                 "seq_shard": True, "grad_accum": 4},
    "qwen1.5-32b":              {"subquadratic": False, "fsdp": False,
                                 "seq_shard": True, "grad_accum": 4},
    "qwen2.5-32b":              {"subquadratic": False, "fsdp": False,
                                 "seq_shard": True, "grad_accum": 4},
    "phi3-mini-3.8b":           {"subquadratic": False, "fsdp": False,
                                 "seq_shard": True, "grad_accum": 1},
    "deepseek-v2-236b":         {"subquadratic": False, "fsdp": True,
                                 "seq_shard": True, "grad_accum": 16,
                                 "moments": "bfloat16"},
    "llama4-maverick-400b-a17b": {"subquadratic": False, "fsdp": True,
                                  "seq_shard": True, "grad_accum": 8,
                                  "moments": "bfloat16"},
    "musicgen-large":           {"subquadratic": False, "fsdp": False,
                                 "seq_shard": True, "grad_accum": 4},
    "zamba2-2.7b":              {"subquadratic": True,  "fsdp": False,
                                 "seq_shard": True, "grad_accum": 4},
    "internvl2-26b":            {"subquadratic": False, "fsdp": False,
                                 "seq_shard": True, "grad_accum": 4},
    "quickstart":               {"subquadratic": False, "fsdp": False,
                                 "seq_shard": False, "grad_accum": 1},
}


def get_config(name: str, smoke: Optional[bool] = None) -> ModelConfig:
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name}; known: {sorted(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[name]}")
    return mod.smoke() if smoke else mod.full()


def cells(include_skipped: bool = False):
    """All (arch, shape) dry-run cells, honoring the long_500k skip rule
    for pure full-attention archs (see DESIGN.md SS5)."""
    out = []
    for a in ARCHS:
        for s in SHAPES:
            skipped = (s == "long_500k" and not META[a]["subquadratic"])
            if skipped and not include_skipped:
                continue
            out.append((a, s, skipped))
    return out
