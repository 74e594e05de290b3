"""gpt2-small-f32 and its kin -> a ``PagedTransformerDecoder`` over a
``KVBlockPool`` sized as the configuration's ``serving`` block says."""
from __future__ import annotations


def decoder(cfg, weights, name="bench"):
    from mxnet_tpu.serving import KVBlockPool, PagedTransformerDecoder
    m, s = cfg["model"], cfg["serving"]
    pool = KVBlockPool(m["n_layer"], m["n_head"], m["n_embd"] // m["n_head"],
                       num_pages=s["pool_pages"], page_size=s["page_tokens"],
                       name=name + ".kv")
    config = dict(vocab_size=m["vocab_size"], embed_dim=m["n_embd"],
                  num_heads=m["n_head"], num_layers=m["n_layer"],
                  ffn_dim=m["n_inner"], seq_len=m["n_positions"])
    dec = PagedTransformerDecoder(weights, config, slot_count=s["slots"],
                                  pool=pool, max_len=s["max_len"], name=name)
    return dec, pool
