"""BERT masked-LM as a ``paddle.static`` training Program (copy of
``bench.py::build_bert_static``, which may change or go): post-norm
blocks, tanh GELU, no final LayerNorm, a plain Linear head under
``F.cross_entropy`` — the op chains the Executor's fusion tier realises
(linear+add+layer_norm around each residual) and the fused Adam.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
# the same widths need the same operations, whichever entry point runs them
from bert import attention_calls, train_flops_per_token  # noqa: E402,F401
import check  # noqa: E402


def build(cfg, variant, batch, seq, opt_cfg):
    """-> (program, loss variable, {reference leaf: Parameter}, constant
    feeds by name)."""
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import nn, optimizer

    if variant.get("final_norm") or variant.get("gelu") != "tanh":
        raise ValueError("models/bert_static.py builds tanh GELU and no "
                         f"final LayerNorm, the cell asks for {variant}")
    if opt_cfg["name"] != "adam":
        raise ValueError("models/bert_static.py minimises with Adam")
    V, H, heads = (cfg["vocab_size"], cfg["hidden_size"],
                   cfg["num_attention_heads"])
    hd, ffn = H // heads, cfg["intermediate_size"]
    eps = cfg["layer_norm_eps"]
    leaves = {}

    def keep(layer, name, block=None, w="w"):
        leaves[check.key_of(f"{name}.{w}", block)] = layer.weight
        leaves[check.key_of(f"{name}.b", block)] = layer.bias
        return layer

    main = paddle.static.Program()
    with paddle.static.program_guard(main):
        ids = paddle.static.data("ids", [batch, seq], "int64")
        labels = paddle.static.data("labels", [batch, seq], "int64")
        tok = nn.Embedding(V, H)
        pos = nn.Embedding(cfg["max_position_embeddings"], H)
        leaves["tok"], leaves["pos"] = tok.weight, pos.weight
        # position ids are fed, as PaddleNLP's static BERT takes them: on
        # ``paddle.arange`` the look-up is folded at record time and the
        # position table would be a constant that never trains
        pos_ids = paddle.static.data("pos_ids", [1, seq], "int64")
        x = tok(ids) + pos(pos_ids)
        for i in range(cfg["num_hidden_layers"]):
            wq = keep(nn.Linear(H, H), "layers.q", i)
            wk = keep(nn.Linear(H, H), "layers.k", i)
            wv = keep(nn.Linear(H, H), "layers.v", i)
            proj = keep(nn.Linear(H, H), "layers.o", i)
            ln1 = keep(nn.LayerNorm(H, epsilon=eps), "layers.ln1", i, "g")
            fc1 = keep(nn.Linear(H, ffn), "layers.fc1", i)
            fc2 = keep(nn.Linear(ffn, H), "layers.fc2", i)
            ln2 = keep(nn.LayerNorm(H, epsilon=eps), "layers.ln2", i, "g")
            q = wq(x).reshape([batch, seq, heads, hd])
            k = wk(x).reshape([batch, seq, heads, hd])
            v = wv(x).reshape([batch, seq, heads, hd])
            a = F.scaled_dot_product_attention(q, k, v)
            x = ln1(proj(a.reshape([batch, seq, H])) + x)
            h = F.gelu(fc1(x), approximate=True)
            x = ln2(fc2(h) + x)
        head = keep(nn.Linear(H, V), "head")
        logits = head(x)
        loss = F.cross_entropy(logits.reshape([-1, V]),
                               labels.reshape([-1]))
        optimizer.Adam(learning_rate=opt_cfg["lr"], beta1=opt_cfg["beta1"],
                       beta2=opt_cfg["beta2"],
                       epsilon=opt_cfg["eps"]).minimize(loss)
    return main, loss, leaves, {
        "pos_ids": np.arange(seq, dtype=np.int32)[None]}
