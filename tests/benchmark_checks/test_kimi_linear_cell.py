"""The tiny cut of ``kimi_linear_48b_a3b_es`` and the scopes its cell's run
loop has to carry, entered at import into the tables the accepted files read
(``_bench_tiny.TINY_CONFIG``, ``test_scoped.PARTS``): pytest imports every
module of this directory before it runs a test, in every worker, so the
accepted files' own parametrisations pick the cell up at tiny sizes with no
edit (``test_run_end_to_end``, ``test_run_loop_carries_every_scope...``,
``test_control_is_not_correct``, ``test_fault_under_the_timed_path_...``).
Run ``test_harness.py`` or ``test_correct.py`` ALONE and this module is not
imported: the cell's cases then find no cut and would build the model at its
published widths. Run the directory. (A ``benchmark`` PR that lets
``conftest.py`` read tiny cuts from data files ends this: ROADMAP D10.)

Each shape the cut changes is a top-level key of the configuration's file,
``linear_attn_config`` whole: ``tiny_checkout`` does a shallow ``update``.
"""

from __future__ import annotations

import json

import _bench_tiny
import test_scoped
from benchmark.lib import manifest as mf

CONFIG, CELL = "kimi_linear_48b_a3b_es", "kimi_linear_es_pop64_seq2k"

# hidden 64, 2 heads (MLA 16 + 8 / 16; KDA 2 x 16, 4 taps), 8 experts of which
# 2 held, top 2, 5 layers (KDA, KDA, KDA, MLA, KDA), vocabulary 256 of which 32 held
_bench_tiny.TINY_CONFIG[CONFIG] = {
    "hidden_size": 64,
    "num_attention_heads": 2,
    "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8,
    "v_head_dim": 16,
    "kv_lora_rank": 24,
    "intermediate_size": 96,
    "moe_intermediate_size": 32,
    "num_experts": 2,
    "num_experts_published": 8,
    "experts_held": [0, 2],
    "num_experts_per_token": 2,
    "vocab_size": 32,
    "vocab_size_published": 256,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27],
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26],
        "num_heads": 2, "head_dim": 16, "short_conv_kernel_size": 4,
    },
    "blocks": {"chunk_pairs": 4, "attn_block_pairs": 2, "kda_block_pairs": 2, "dense_block_pairs": 2,
               "shared_block_pairs": 4, "expert_block_rows": 16},
}

_n = test_scoped.names
test_scoped.PARTS[CELL] = [
    (_n.ASK, _n.NOISE), (_n.ASK, _n.CAST),
    (_n.TELL, _n.FIT_TRANSFORMS), (_n.TELL, _n.GRADIENT), (_n.TELL, _n.UPDATE),
    (_n.EVALUATE, "lm"),
    *((_n.EVALUATE, part.split("/")[1]) for part in (
        _n.LM_FORWARD, _n.LM_EMBED, _n.LM_ATTENTION, _n.LM_KDA, _n.LM_KDA_SCAN, _n.LM_MLP, _n.LM_ROUTER,
        _n.LM_EXPERTS, _n.LM_LOWRANK, _n.LM_HEAD_LOSS)),
]


def test_the_cut_changes_shapes_and_nothing_else(tmp_path):
    """The tiny checkout's file is the real one but for the cut's keys, and
    the cut keeps the pattern (layers counted from 1) and what ``reduced``
    lists."""
    real = mf.load_json(mf.ROOT, f"benchmark/configs/{CONFIG}.json")
    root = _bench_tiny.tiny_checkout(tmp_path)
    tiny = json.loads((root / "benchmark" / "configs" / f"{CONFIG}.json").read_text())
    cut = _bench_tiny.TINY_CONFIG[CONFIG]
    assert {k for k in real if real[k] != tiny[k]} == set(cut) and set(tiny) == set(real)
    for key in ("kda_layers", "full_attn_layers"):
        assert cut["linear_attn_config"][key] == real["linear_attn_config"][key]
    assert tiny["layers"] == real["layers"] == 5 and tiny["limits"] == real["limits"]


def test_the_cells_scopes_are_the_cores_names():
    parts = {part for _, part in test_scoped.PARTS[CELL]}
    assert {"kda", "kda_scan", "attention", "lowrank"} <= parts
    assert _n.LM_KDA == "lm/kda" and _n.LM_KDA_SCAN == "lm/kda_scan"
