"""Latent-decode kernel: the least time the chip needs for the bytes
absorbed latent decode needs (every decoded token's live latent rows at
their stored format, without padding, plus its absorbed query and its
latent output, all layers; ``bench/arith_mla.py``) at the HBM peak, over
the device time of the Mosaic calls inside the decode program
(``jit__step``) that read the latent pool (an operand shaped ``[pages,
page, width]`` with the row's stored width), in percent.  ``None`` where
no such call ran."""
import re

from bench import arith_mla, harness


def read(run):
    if run.trace is None or not run.trace_tokens:
        return None
    a = arith_mla.MlaWorkload.from_conf(harness.load_cell(run.cell).conf)
    width = -(-(a.rank + a.rope) // 128) * 128
    pool = re.compile(r"\[\d+,\d+,%d\]" % width)
    ops = [o for o in run.trace.ops_within(run.trace.programs("jit__step"))
           if 'custom_call_target="tpu_custom_call"' in o.name
           and pool.search(o.name.split("custom-call(", 1)[-1])]
    kernel_ns = sum(o.dur for o in ops)
    if kernel_ns <= 0:
        return None
    need = sum(a.latent_decode_bytes(p + j) for p, j in run.trace_tokens
               if j > 0)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] * 1e9 / kernel_ns
