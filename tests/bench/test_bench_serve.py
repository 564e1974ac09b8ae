"""The chat-decode cell rehearsed on the CPU at a tiny size: a sound run
is correct, and each fault the serving path can have comes out not
correct."""
import pytest

import tiny_cells

CELL = "phi3.chat-decode-c8"


def test_sound_run_is_correct():
    line = tiny_cells.run(CELL)
    assert line["correct"], line["checks"]
    m = line["metrics"]
    assert set(m) == {"output_tokens_per_s", "setup_s"}
    assert m["output_tokens_per_s"]["value"] > 0
    assert line["attempted"] >= 4


def test_traced_run_reports_the_layer_metrics():
    line = tiny_cells.run(CELL, trace=1)
    assert line["correct"], line["checks"]
    m = line["metrics"]
    assert {"device_idle.serve", "host_ms_per_step.serve",
            "mfu.serve"} <= set(m)
    assert "device_idle.gasket" not in m
    assert 0 < m["mfu.serve"]["value"] < 100


def test_control_is_not_correct():
    """The float8 control against the cell's own limit, at the smallest
    width where its rounding shows through four layers."""
    wider = {"hidden_size": 128, "intermediate_size": 256,
             "num_hidden_layers": 4}
    kw = dict(config=wider, seed=2 ** 31 + 5, seconds=0.6,
              output_lens=[6, 12])
    sound = tiny_cells.run(CELL, **kw)
    control = tiny_cells.run(CELL, control=1, **kw)
    assert sound["correct"] and not control["correct"]


def _pools_unchanged(orig):
    def step(params, inputs, pools, page_table, pos, active, cfg):
        logits, _ = orig(params, inputs, pools, page_table, pos, active,
                         cfg=cfg)
        return logits, pools
    return step


def _half_batch(orig):
    def step(params, inputs, pools, page_table, pos, active, cfg):
        logits, pools = orig(params, inputs, pools, page_table, pos, active,
                             cfg=cfg)
        half = logits.shape[0] // 2
        return logits.at[half:].set(0), pools
    return step


@pytest.mark.parametrize("fault", [_pools_unchanged, _half_batch])
def test_a_broken_decode_step_is_not_correct(monkeypatch, fault):
    from repro.models import model as model_lib
    monkeypatch.setattr(model_lib, "decode_step_paged",
                        fault(model_lib.decode_step_paged))
    assert not tiny_cells.run(CELL)["correct"]


def test_an_altered_token_is_not_correct(monkeypatch):
    from repro.launch.serve import PagedServer
    orig = PagedServer._sample_token

    def altered(self, logits_row, rid, pos):
        return (orig(self, logits_row, rid, pos) + 1) % logits_row.shape[-1]
    monkeypatch.setattr(PagedServer, "_sample_token", altered)
    assert not tiny_cells.run(CELL)["correct"]
