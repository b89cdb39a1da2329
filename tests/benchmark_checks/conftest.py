"""The tiny cut of the configurations and traffic mixes that ``_bench_tiny``
does not know: entered into its two tables here, before the test modules are
imported, so that ``tiny_checkout`` cuts them like the others and every cell
of ``BENCHMARK.json`` runs on the CPU in seconds. Each shape a cut changes is
a top-level key of the file (``tiny_checkout`` does a shallow ``update``)."""

import _bench_tiny

_bench_tiny.TINY_CONFIG["moonlight_16b_a3b_es"] = {
    # hidden 64, 2 heads, 8 experts of which 2 held, top 2, 5 layers,
    # vocabulary 256 of which 32 held
    "hidden_size": 64,
    "num_attention_heads": 2,
    "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8,
    "v_head_dim": 16,
    "kv_lora_rank": 24,
    "intermediate_size": 96,
    "moe_intermediate_size": 32,
    "n_routed_experts": 2,
    "n_routed_experts_published": 8,
    "experts_held": [0, 2],
    "num_experts_per_tok": 2,
    "vocab_size": 32,
    "vocab_size_published": 256,
    "blocks": {"chunk_pairs": 4, "attn_block_pairs": 2, "dense_block_pairs": 2, "shared_block_pairs": 4,
               "expert_block_rows": 16},
}
_bench_tiny.TINY_TRAFFIC["closed_pop64_seq2048_g1"] = {
    "pop": 16,
    "seq_len": 64,
    "doc_len_median": 12,
    "doc_len_min": 4,
}


def pytest_collection_modifyitems(session, config, items):
    """``test_scoped.py`` lists, for each cell, the part scopes its run loop
    has to carry; the file is the accepted benchmark's, so the new cell's
    list is entered here, once the module is imported. ``scoped.under`` reads
    a scope's name a component at a time: ``lm/attention`` is ``lm``, then
    ``attention``."""
    import sys

    module = sys.modules.get("test_scoped")
    if module is None:
        return
    n = module.names
    module.PARTS.setdefault("moonlight_es_pop64_seq2k", [
        (n.ASK, n.NOISE), (n.ASK, n.CAST),
        (n.TELL, n.FIT_TRANSFORMS), (n.TELL, n.GRADIENT), (n.TELL, n.UPDATE),
        (n.EVALUATE, "lm"),
        *((n.EVALUATE, part.split("/")[1]) for part in (
            n.LM_FORWARD, n.LM_EMBED, n.LM_ATTENTION, n.LM_MLP, n.LM_ROUTER, n.LM_EXPERTS, n.LM_LOWRANK,
            n.LM_HEAD_LOSS)),
    ])
