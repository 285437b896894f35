"""Whole model step: the operations the model needs for the tokens the
traced stretch emitted (each decoded token: 2 x matmul parameters + both
attention products over its context; each prefill: its whole causal
prompt), over the stretch's seconds and the chip's bf16 peak, in percent.
Recomputed or padded work does not count (``bench/arith.py``)."""


def read(run):
    if run.trace is None or not run.trace_tokens or run.trace_s <= 0:
        return None
    a = run.arith
    flops = sum(a.prefill_flops(p) if j == 0 else a.decode_flops(p + j)
                for p, j in run.trace_tokens)
    return 100.0 * flops / (run.trace_s * run.peaks["bf16_flops"])
