"""Roofline time of the flash-attention calls one step executes, over
the device time of the step's Mosaic calls, in percent.  Read only in a
cell whose file says its Mosaic calls are flash attention
(``"mosaic_is": "flash_attention"``) and whose trace holds some.

What the algorithm needs, from shapes: a forward call is two matmuls of
2*B*H*S*S*D FLOPs each (scores, values) and reads q, k, v and writes o;
a backward call is five (scores again, dV, dP, dQ, dK) and reads q, k, v,
o, do and writes dq, dk, dv.  Causal counts half the square.  A forward
replayed by recompute is counted as executed (``forward_replays`` of the
model file's ``attention_calls``).  What the kernels recompute beyond
that is not needed and lowers the share.
"""
import trace_reduce


def roofline_seconds(calls, peaks, itemsize=2):
    """-> (seconds, which bound binds) for one step's attention calls."""
    B, H, S, D = (calls[k] for k in ("batch", "heads", "seq", "head_dim"))
    matmul = 2 * B * H * S * S * D * (0.5 if calls["causal"] else 1.0)
    forwards = 1 + calls["forward_replays"]
    flops = calls["calls"] * matmul * (2 * forwards + 5)
    tensor = B * S * H * D * itemsize
    bytes_ = calls["calls"] * tensor * (4 * forwards + 8)
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), ("compute" if by_flops >= by_bytes
                                     else "memory")


def read(ctx):
    if (ctx["cell"].get("mosaic_is") != "flash_attention"
            or not ctx["trace"]["module_runs"]):
        return None
    took = trace_reduce.mosaic_seconds_per_run(ctx["trace"])
    if took is None:
        return None
    mix = ctx["mix"]
    calls = ctx["model"].attention_calls(ctx["cfg"], mix["batch"],
                                         mix["seq"])
    need, bound = roofline_seconds(calls, ctx["peaks"])
    ctx["log"](f"[flash_attn_roofline] needs {need * 1000:.3f} ms a step "
               f"({bound}-bound), took {took * 1000:.3f} ms")
    return need / took * 100
