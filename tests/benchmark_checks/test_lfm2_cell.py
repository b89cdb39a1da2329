"""The tiny cut of ``lfm2_24b_a2b_es`` and of its traffic, and the scopes its
cell's run loop has to carry, entered at import into the tables the accepted
files read (``_bench_tiny.TINY_CONFIG``, ``_bench_tiny.TINY_TRAFFIC``,
``test_scoped.PARTS``): pytest imports every module of this directory before
it runs a test, in every worker, so the accepted files' own parametrisations
pick the cell up at tiny sizes with no edit (``test_run_end_to_end``,
``test_run_loop_carries_every_scope...``, ``test_control_is_not_correct``,
``test_fault_under_the_timed_path_...``). Run ``test_harness.py`` or
``test_correct.py`` ALONE and this module is not imported: the cell's cases
then find no cut and would build the model at its published widths. Run the
directory. (A ``benchmark`` PR that lets ``conftest.py`` read tiny cuts from
data files ends this: ROADMAP D10.)

Each shape the cut changes is a top-level key of the configuration's file:
``tiny_checkout`` does a shallow ``update``.
"""

from __future__ import annotations

import json

import _bench_tiny
import test_scoped
from benchmark.lib import manifest as mf

CONFIG, CELL, TRAFFIC = "lfm2_24b_a2b_es", "lfm2_es_pop16_seq8k", "closed_pop16_seq8192_g1"

# hidden 64, 4 query heads on 2 key-value heads of 16, 8 experts of which 2 held, top 2, layers 1 to 5 of the
# published pattern (conv + dense MLP; attention, conv, conv, conv + experts), vocabulary 256 of which 32 held
_bench_tiny.TINY_CONFIG[CONFIG] = {
    "hidden_size": 64,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "intermediate_size": 96,
    "moe_intermediate_size": 32,
    "num_experts": 2,
    "num_experts_published": 8,
    "experts_held": [0, 2],
    "num_experts_per_tok": 2,
    "vocab_size": 32,
    "vocab_size_published": 256,
    "blocks": {"chunk_pairs": 4, "attn_block_pairs": 2, "dense_block_pairs": 2, "expert_block_rows": 16},
}
# the other language cells' tiny row at half their members: 8 on 64 tokens, documents of median 12
_bench_tiny.TINY_TRAFFIC[TRAFFIC] = {"pop": 8, "seq_len": 64, "doc_len_median": 12, "doc_len_min": 4}

_n = test_scoped.names
test_scoped.PARTS[CELL] = [
    (_n.ASK, _n.NOISE), (_n.ASK, _n.CAST),
    (_n.TELL, _n.FIT_TRANSFORMS), (_n.TELL, _n.GRADIENT), (_n.TELL, _n.UPDATE),
    (_n.EVALUATE, "lm"),
    *((_n.EVALUATE, part.split("/")[1]) for part in (
        _n.LM_FORWARD, _n.LM_EMBED, _n.LM_ATTENTION, _n.LM_CONV, _n.LM_MLP, _n.LM_ROUTER, _n.LM_EXPERTS,
        _n.LM_LOWRANK, _n.LM_HEAD_LOSS)),
]


def test_the_cut_changes_shapes_and_nothing_else(tmp_path):
    """The tiny checkout's files are the real ones but for the cut's keys, and
    the cut keeps the pattern, the layers held (counted from 0), the taps,
    the precision, the limits and what ``reduced`` lists."""
    real = mf.load_json(mf.ROOT, f"benchmark/configs/{CONFIG}.json")
    root = _bench_tiny.tiny_checkout(tmp_path)
    tiny = json.loads((root / "benchmark" / "configs" / f"{CONFIG}.json").read_text())
    cut = _bench_tiny.TINY_CONFIG[CONFIG]
    assert {k for k in real if real[k] != tiny[k]} == set(cut) and set(tiny) == set(real)
    for key in ("layer_types", "layers_held", "num_dense_layers", "conv_L_cache", "limits", "reduced",
                "compute_dtype", "center_dtype", "rank", "noise_stdev", "learning_rate", "faults"):
        assert tiny[key] == real[key]
    assert tiny["layers"] == real["layers"] == 5
    real = mf.load_json(mf.ROOT, f"benchmark/traffic/{TRAFFIC}.json")
    tiny = json.loads((root / "benchmark" / "traffic" / f"{TRAFFIC}.json").read_text())
    assert {k for k in real if real[k] != tiny[k]} == set(_bench_tiny.TINY_TRAFFIC[TRAFFIC])


def test_the_cells_scopes_are_the_cores_names():
    parts = {part for _, part in test_scoped.PARTS[CELL]}
    assert {"conv", "attention", "lowrank"} <= parts and not {"kda", "kda_scan"} & parts
    assert _n.LM_CONV == "lm/conv"
