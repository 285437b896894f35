"""Decode-attention kernel: the least time the chip needs for the bytes
decode attention needs (every decoded token's live K and V at their stored
format, plus its query and output at the activations' format, all layers;
``bench/arith.py``) at the HBM peak, over the device time of the
decode-attention kernel events, in percent.

The kernel events are the Mosaic custom calls inside the decode program
(``jit__step``) that read a K/V operand shaped ``[.., .., kv_heads,
head_dim]``: ``flash_decode`` over the gathered view, or ``paged_decode``
over the pool, whichever ran.  The gather that feeds ``flash_decode`` is a
separate XLA operation and is not counted here."""
import re


def read(run):
    if run.trace is None or not run.trace_tokens:
        return None
    a = run.arith
    kv = re.compile(r"\[\d+,\d+,%d,%d\]" % (a.kv_heads, a.head_dim))
    ops = [o for o in run.trace.ops_within(run.trace.programs("jit__step"))
           if 'custom_call_target="tpu_custom_call"' in o.name
           and kv.search(o.name.split("custom-call(", 1)[-1])]
    kernel_ns = sum(o.dur for o in ops)
    if kernel_ns <= 0:
        return None
    need = sum(a.decode_attn_bytes(p + j) for p, j in run.trace_tokens
               if j > 0)
    least_ns = need / run.peaks["hbm_bytes_per_s"] * 1e9
    return 100.0 * least_ns / kernel_ns
