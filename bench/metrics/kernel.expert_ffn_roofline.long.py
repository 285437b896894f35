"""Expert layer: the least time the chip needs for the bytes the decode
steps' expert FFNs need (the three matrices of each held expert that a
step's tokens hit, from the engine's ``moe_experts_hit`` counter; every
MoE layer's shared experts once a step; the token rows in and out, from
``moe_routed_here``; ``bench/arith_mla.py``) at the HBM peak, over the
device time in which the decode program (``jit__step``) works on expert
weights, in percent.

The expert operations are found by an expert weight among their operands
(found by its shape: a held expert stack or a shared-expert matrix): the
grouped matmuls, the shared experts' fusions, and the asynchronous copies
by which the compiler fetches weights into on-chip memory ahead of their
use.  Such a copy moves its bytes between its start and its done
operation while other operations run, so its time is that whole window;
the time is the union of the windows and the other operations' own
spans.  Counting the experts hit, not all held ones, keeps a kernel that
skips idle experts under 100 %.  ``None`` where the program writes no
counters or no such operation ran."""
import re

from bench import arith_mla, harness
from bench.trace import union

_NAME = re.compile(r"^%(\S+) = ")
_ASYNC = re.compile(r"^%([\w-]+)-(start|done)\b")


def expert_ns(ops, shapes) -> float:
    """Device nanoseconds covered by the operations on expert weights in
    ``ops`` (sorted or not), each asynchronous start paired with its done
    operation (which names the start among its operands)."""
    spans, started = [], {}
    for o in sorted(ops, key=lambda e: e.start):
        if not any(s in o.name for s in shapes):
            continue
        m, kind = _NAME.match(o.name), _ASYNC.match(o.name)
        if kind and kind.group(2) == "start" and m:
            started[m.group(1)] = o
            continue
        if kind and kind.group(2) == "done":
            args = o.name.split("(", 1)[-1]
            pair = [n for n in started if re.search(
                r"%" + re.escape(n) + r"(?![\w.])", args)]
            if pair:
                spans.append((started.pop(pair[0]).start, o.end))
                continue
        spans.append((o.start, o.end))
    spans += [(o.start, o.end) for o in started.values()]
    return sum(b - a for a, b in union(spans))


def read(run):
    if run.trace is None or not run.trace_tokens:
        return None
    a = arith_mla.MlaWorkload.from_conf(harness.load_cell(run.cell).conf)
    c = arith_mla.read_counters(run.trace.host)
    if "moe_experts_hit.decode" not in c:
        return None
    steps = run.trace.programs("jit__step")
    sh = a.shared * a.moe_ff
    shapes = (f"[{a.held},{a.d},{a.moe_ff}]", f"[{a.held},{a.moe_ff},{a.d}]",
              f"[{a.d},{sh}]", f"[{sh},{a.d}]")
    ns = expert_ns(run.trace.ops_within(steps), shapes)
    if ns <= 0:
        return None
    tokens = sum(1 for _, j in run.trace_tokens if j > 0)
    need = a.expert_step_bytes(c["moe_experts_hit.decode"],
                               c.get("moe_routed_here.decode", 0), tokens,
                               len(steps))
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] * 1e9 / ns
