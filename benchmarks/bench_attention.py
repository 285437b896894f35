"""Decode-step attention benchmark: packed KV cache vs f32, per backend.

``collect()`` produces schema-stable entries for every (paper KV format x
attention backend) cell, where the backend axis is the registry's FULL
legal-spelling list (``kernels/dispatch.legal_impls()``): ``xla`` (the
dequantize path; its jitted wall time is the honest CPU baseline), the
fused ``flash_pallas`` kernel, the block-table ``paged`` kernel (reported
with its page size and pool internal fragmentation), and every
``flash_shmap`` composition.  Deriving the axis from the registry is
deliberate -- a backend added to ``dispatch.py`` shows up here (and in the
CI bench smoke, which executes every spelling in interpret mode) without
anyone remembering to extend a list, and ``benchmarks/run.py`` fails the
smoke if a spelling ever goes missing.  ``run.py`` aggregates the entries
into ``BENCH_attention.json`` at the repo root so the perf trajectory is
diffable across PRs.

Off TPU the Pallas kernels run in interpret mode, so their wall time is
meaningless and recorded only when explicitly requested (``--time-interpret``
/ the CI smoke run, flagged ``"interpret": true``); the HBM-byte columns are
analytic and platform-independent (the paper's Fig. 6 memory-access
reduction on the serving hot path), with XLA ``cost_analysis`` bytes as
evidence that the dequantize path really materializes the wide copy.

``python -m benchmarks.bench_attention [--time-interpret]`` for a
standalone table; ``report()`` feeds the benchmarks/run.py CSV.
"""
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import PAPER_FORMATS
from repro.core.policy import transprecision_policy
from repro.core.qtensor import encode
from repro.kernels import dispatch
from repro.kernels.flash_attention import (attention_hbm_bytes,
                                           flash_decode_reference,
                                           ring_ppermute_bytes)
from repro.kernels.paged_attention import (paged_hbm_bytes,
                                           paged_ring_ppermute_bytes)
from repro.kernels.paged_cache import (DEFAULT_PAGE_SIZE,
                                       paged_view_of_contiguous,
                                       pool_fragmentation)

# decode_32k-flavoured cell scaled for CPU: 4 seqs x 4k tokens, 8 KV heads
B, S, H, G, DH = 4, 4096, 8, 4, 64

# reference ring topology for the analytic ppermute-payload column: the
# bench runs meshless (wrappers fall back), so the per-step interconnect
# bytes of the ring rows are reported for the smallest real ring -- the
# same 2-device host mesh the conformance suite pins the numerics on
RING_DEVICES = 2

# every legal registry spelling (includes the bare "flash_shmap" alias of
# "flash_shmap+xla": executing the alias is how the bench locks down that
# canonicalization keeps working)
IMPLS = tuple(dispatch.legal_impls())


def _time_us(fn, *args, reps=3):
    jax.block_until_ready(fn(*args))  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6


def collect(b=B, s=S, h=H, g=G, dh=DH, *, impls=IMPLS,
            time_interpret: bool = False) -> list:
    """Benchmark entries (dicts) for every (format x backend) cell."""
    # the model-level backends register themselves at attention import
    import repro.models.attention  # noqa: F401

    entries = []
    shape = f"B{b}_S{s}_H{h}_G{g}_dh{dh}"
    page = max(8, min(DEFAULT_PAGE_SIZE, s))
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, h, g, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h, dh)), jnp.float32)
    # ragged row 0 (s - page/2 valid tokens) so the paged rows report a
    # non-trivial pool fragmentation instead of a structural 0.0
    len_np = np.full((b,), s, np.int64)
    len_np[0] = s - page // 2
    lengths = jnp.asarray(len_np, jnp.int32)
    bytes_f32 = attention_hbm_bytes(b, s, h, dh, None, g=g)
    on_tpu = jax.default_backend() == "tpu"

    for fmt in PAPER_FORMATS:
        kp, vp = encode(k, fmt), encode(v, fmt)
        bytes_packed = attention_hbm_bytes(b, s, h, dh, fmt, g=g)
        bytes_paged = paged_hbm_bytes(b, len_np, h, dh, fmt, page_size=page,
                                      g=g)
        pol = transprecision_policy(kv_fmt=fmt)
        ck = jax.lax.bitcast_convert_type(kp, fmt.native_dtype)
        cv = jax.lax.bitcast_convert_type(vp, fmt.native_dtype)

        for impl in impls:
            parts = dispatch.canonicalize_impl(impl)
            paged = parts[-1] == "paged"
            kv_bytes = (bytes_f32 if impl == "xla"
                        else bytes_paged if paged else bytes_packed)
            entry = {
                "bench": "attention_decode",
                "shape": shape,
                "impl": impl,
                "fmt": fmt.name,
                "hbm_bytes": kv_bytes,
                "bytes_vs_f32": round(bytes_f32 / kv_bytes, 2),
                "ms_per_step": None,
                "interpret": (not on_tpu) and impl != "xla",
            }
            if paged:
                # block-table layout costs: page granule, whole-page
                # fetches (counted in hbm_bytes above) and the fraction of
                # allocated pool slots holding no valid token
                entry["page_size"] = page
                entry["pool_frag"] = round(
                    pool_fragmentation(len_np, page), 4)
            if "ring" in parts:
                # per-step interconnect payload one device rotates around
                # the RING_DEVICES-way ring, next to the HBM bytes it
                # streams: packed containers shrink both by the same ratio
                if paged:
                    pool_pages = b * (-(-s // page))
                    entry["ppermute_bytes"] = paged_ring_ppermute_bytes(
                        pool_pages, page, h, dh, fmt,
                        n_devices=RING_DEVICES)
                else:
                    entry["ppermute_bytes"] = ring_ppermute_bytes(
                        b, s, h, dh, fmt, n_devices=RING_DEVICES)
                entry["ring_devices"] = RING_DEVICES
            if impl == "xla":
                ref = jax.jit(lambda qq, kk, vv, ll, fmt=fmt:
                              flash_decode_reference(qq, kk, vv, fmt, ll))
                entry["ms_per_step"] = round(
                    _time_us(ref, q, kp, vp, lengths) / 1e3, 3)
                cost = ref.lower(q, kp, vp, lengths).compile().cost_analysis()
                entry["xla_bytes_accessed"] = int(
                    cost.get("bytes accessed", 0))
            elif on_tpu or time_interpret:
                fn = dispatch.resolve_decode(impl)
                if paged:
                    kpg, vpg, tbl = paged_view_of_contiguous(ck, cv, page)
                    us = _time_us(
                        lambda qq, kk, vv, ll, tt, fn=fn, pol=pol:
                        fn(qq, kk, vv, ll, scale=float(1 / np.sqrt(dh)),
                           policy=pol, block_tables=tt),
                        q, kpg, vpg, lengths, tbl, reps=1)
                else:
                    us = _time_us(
                        lambda qq, kk, vv, ll, fn=fn, pol=pol:
                        fn(qq, kk, vv, ll, scale=float(1 / np.sqrt(dh)),
                           policy=pol), q, ck, cv, lengths, reps=1)
                entry["ms_per_step"] = round(us / 1e3, 3)
            entries.append(entry)
    return entries


def report(time_interpret: bool = False, entries=None) -> list:
    """Legacy CSV rows (name, us_per_call, derived) from collect()."""
    if entries is None:
        entries = collect(time_interpret=time_interpret)
    by_fmt = {}
    for e in entries:
        by_fmt.setdefault(e["fmt"], {})[e["impl"]] = e
    rows = []
    for fmt_name, impls in by_fmt.items():
        xla = impls.get("xla")
        if xla is None:
            continue
        packed = impls.get("flash_pallas", xla)
        derived = (f"kv_hbm_bytes={packed['hbm_bytes']}"
                   f";f32_hbm_bytes={xla['hbm_bytes']}"
                   f";bytes_ratio={packed['bytes_vs_f32']:.2f}"
                   f";xla_dequant_bytes_accessed="
                   f"{xla.get('xla_bytes_accessed', 0)}")
        if packed.get("ms_per_step") is not None and packed is not xla:
            derived += f";interpret_us={packed['ms_per_step'] * 1e3:.0f}"
        rows.append((f"attn_decode_{fmt_name}",
                     (xla["ms_per_step"] or 0.0) * 1e3, derived))
    return rows


def main():
    entries = collect(time_interpret="--time-interpret" in sys.argv)
    rows = report(entries=entries)
    print(f"decode step: B={B} S={S} n_kv={H} G={G} dh={DH} "
          f"(q/scores f32; cache packed)")
    print(f"{'kv format':<14} {'xla decode us':>14} {'kv HBM bytes':>14} "
          f"{'vs f32':>8}")
    for name, us, derived in rows:
        d = dict(kv.split("=") for kv in derived.split(";"))
        print(f"{name[12:]:<14} {us:>14.0f} {d['kv_hbm_bytes']:>14} "
              f"{float(d['bytes_ratio']):>7.2f}x"
              + (f"  interpret_us={d['interpret_us']}"
                 if "interpret_us" in d else ""))
    print("\n(bytes = K+V payload + query per step; the flash kernel "
          "moves exactly kv_hbm_bytes, the XLA path additionally "
          "materializes the f32 dequantized copy -- see "
          "xla_dequant_bytes_accessed in the CSV row.)")
    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
