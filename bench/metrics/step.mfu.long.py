"""Whole model step: the operations the model needs for the tokens the
traced stretch emitted, over the stretch's seconds and the chip's bf16
peak, in percent.  Each decoded token: 2 x the matmul parameters every
token multiplies by (attention, dense FFN, routers, shared experts, LM
head) plus attention over its context in the published un-absorbed
formulation; each prefill: its whole causal prompt; and the routed
experts 2 x their parameters per (token, held expert) pair the engine's
``moe_routed_here`` counters report (``bench/arith_mla.py``).  ``None``
where the program writes no counters."""
from bench import arith_mla, harness


def read(run):
    if run.trace is None or not run.trace_tokens or run.trace_s <= 0:
        return None
    a = arith_mla.MlaWorkload.from_conf(harness.load_cell(run.cell).conf)
    c = arith_mla.read_counters(run.trace.host)
    if not c:
        return None
    pairs = c.get("moe_routed_here.decode", 0) + \
        c.get("moe_routed_here.prefill", 0)
    flops = a.routed_flops(pairs) + sum(
        a.prefill_flops(p) if j == 0 else a.decode_flops(p + j)
        for p, j in run.trace_tokens)
    return 100.0 * flops / (run.trace_s * run.peaks["bf16_flops"])
