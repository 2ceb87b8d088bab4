"""On-chip flash-attention parity record: Mosaic kernels vs dense XLA.

The fast test suite proves the Pallas kernels against the dense oracle in
*interpreter* mode (conftest forces CPU); the Mosaic-compiled path on the
real chip was verified interactively in round 2 but recorded only as a
commit-message claim (VERDICT round-2 weak #7). This tool makes that
verification a regenerable artifact: it runs forward AND gradient parity
for the full feature matrix — causal, sliding window (both sides of the
banding crossover), GQA, key-padding (kv_lens), and the ring-composition
``offset`` — against ``dense_attention`` on whatever backend it's launched
on, and emits one JSON line with per-case max errors and pass/fail.
Round 13 adds a ``fused-vs-split:*`` row per case: the one-pass fused
dq+dk+dv backward (the new default) against the two-kernel split on the
same forward, so the on-chip record covers the fused kernel explicitly.
Rows carry per-row ``device``/``mode`` provenance and
``--write-docs`` MERGES into the committed record: a Mosaic row is
never overwritten by an interpreter rerun, so the round-2 on-chip
record survives off-chip regenerations while new cases land beside it
tagged with the device that actually ran them.

Usage (on the TPU)::

    python -m distributed_tensorflow_tpu.tools.attention_parity \
        --write-docs      # regenerates docs/benchmarks/attention_parity.md

Tolerances are bf16-scale (the kernels do f32 softmax math over bf16 MXU
dots, like XLA's default) — rtol 2e-2 / atol 2e-2 on values whose scale
is O(1); gradients compare at the same bar.
"""

from __future__ import annotations

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

RTOL, ATOL = 2e-2, 2e-2


def _case(name, *, l=512, h=4, hkv=None, d=64, causal=True, window=None,
          kv_lens=None, offset=0, block=None):
    return dict(
        name=name, l=l, h=h, hkv=hkv or h, d=d, causal=causal, window=window,
        kv_lens=kv_lens, offset=offset, block=block,
    )


CASES = [
    _case("causal"),
    _case("noncausal", causal=False),
    _case("causal-block128", block=128),
    _case("window-below-banding", window=256, l=512),  # 4W > L: banding off
    _case("window-banded", window=64, l=1024),  # 4W <= L: banded index maps
    _case("gqa", h=8, hkv=2),
    _case("gqa-window", h=8, hkv=2, window=128, l=1024),
    _case("kv-lens", kv_lens=(301, 444)),
    _case("kv-lens-gqa", h=8, hkv=2, kv_lens=(301, 444)),
    _case("offset-shifted-band", window=96, offset=256, l=512),
]


def run_case(c: dict) -> dict:
    from distributed_tensorflow_tpu.ops.pallas_attention import flash_attention
    from distributed_tensorflow_tpu.ops.ring_attention import dense_attention

    b = 2
    kq, kk, kv, kc = jax.random.split(jax.random.key(7), 4)
    q = jax.random.normal(kq, (b, c["l"], c["h"], c["d"]), jnp.bfloat16)
    k = jax.random.normal(kk, (b, c["l"], c["hkv"], c["d"]), jnp.bfloat16)
    v = jax.random.normal(kv, (b, c["l"], c["hkv"], c["d"]), jnp.bfloat16)
    lens = (
        None if c["kv_lens"] is None else jnp.asarray(c["kv_lens"], jnp.int32)
    )
    kw = dict(
        causal=c["causal"], window=c["window"], kv_lens=lens,
        block_q=c["block"], block_k=c["block"],
    )
    cot = jax.random.normal(kc, q.shape, jnp.float32)

    def flash_fn(q, k, v):
        return flash_attention(q, k, v, offset=c["offset"], **kw)

    def dense_fn(q, k, v):
        # dense_attention has no offset — emulate the shifted band by
        # masking scores directly (the definition offset implements).
        if c["offset"]:
            qf = q.astype(jnp.float32)
            kf = k.astype(jnp.float32)
            kf, vf = kf, v.astype(jnp.float32)
            from distributed_tensorflow_tpu.ops.ring_attention import repeat_kv

            kf, vf = repeat_kv(kf, vf, q.shape[2])
            s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) / np.sqrt(c["d"])
            diff = (
                jnp.arange(c["l"])[:, None] + c["offset"]
                - jnp.arange(c["l"])[None, :]
            )
            mask = diff >= 0
            if c["window"] is not None:
                mask &= diff < c["window"]
            s = jnp.where(mask[None, None], s, -1e30)
            w = jax.nn.softmax(s, axis=-1)
            # Fully-masked rows (offset pushes the whole band past the
            # sequence end): match the kernel's zero-output convention
            # instead of softmax-of-constants garbage, so outputs AND
            # gradients are comparable everywhere.
            row_valid = mask.any(axis=-1)[None, None, :, None]
            w = jnp.where(row_valid, w, 0.0)
            out = jnp.einsum("bhqk,bkhd->bqhd", w, vf)
            return out.astype(q.dtype)
        return dense_attention(
            q, k, v, causal=c["causal"], window=c["window"], kv_lens=lens
        )

    f_out = jax.jit(flash_fn)(q, k, v)
    d_out = jax.jit(dense_fn)(q, k, v)

    def gsum(fn):
        return jax.jit(
            jax.grad(
                lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * cot),
                argnums=(0, 1, 2),
            )
        )(q, k, v)

    g_f, g_d = gsum(flash_fn), gsum(dense_fn)

    # Compare only rows that are not fully masked (padded queries whose
    # whole window lies beyond kv_len are documented garbage on both
    # sides, with different conventions).
    def err(a, b):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        return float(np.max(np.abs(a - b)))

    fwd_err = err(f_out, d_out)
    grad_errs = {n: err(a, b) for n, a, b in zip("qkv", g_f, g_d)}
    tol = ATOL + RTOL  # values are O(1)
    ok = fwd_err < tol and all(e < tol for e in grad_errs.values())
    return {
        "case": c["name"],
        "fwd_max_err": round(fwd_err, 5),
        "dq_max_err": round(grad_errs["q"], 5),
        "dk_max_err": round(grad_errs["k"], 5),
        "dv_max_err": round(grad_errs["v"], 5),
        "ok": bool(ok),
    }


def run_fused_split_case(c: dict) -> dict:
    """Round-13 rows: the fused one-pass backward against the two-kernel
    split on the SAME flash forward — the on-chip record for the new
    kernel (the main rows already run the fused default against dense;
    this isolates fused-vs-split, which should be ~bitwise since both
    accumulate in f32). The round-3 lesson applies verbatim: the CPU
    interpreter tolerates Mosaic-only bugs, so these rows only count
    when the header says Mosaic."""
    from distributed_tensorflow_tpu.ops.pallas_attention import flash_attention

    b = 2
    kq, kk, kv, kc = jax.random.split(jax.random.key(7), 4)
    q = jax.random.normal(kq, (b, c["l"], c["h"], c["d"]), jnp.bfloat16)
    k = jax.random.normal(kk, (b, c["l"], c["hkv"], c["d"]), jnp.bfloat16)
    v = jax.random.normal(kv, (b, c["l"], c["hkv"], c["d"]), jnp.bfloat16)
    lens = (
        None if c["kv_lens"] is None else jnp.asarray(c["kv_lens"], jnp.int32)
    )
    cot = jax.random.normal(kc, q.shape, jnp.float32)
    kw = dict(
        causal=c["causal"], window=c["window"], kv_lens=lens,
        offset=c["offset"], block_q=c["block"], block_k=c["block"],
    )

    def gsum(fused):
        return jax.jit(
            jax.grad(
                lambda q, k, v: jnp.sum(
                    flash_attention(q, k, v, fused=fused, **kw).astype(
                        jnp.float32
                    )
                    * cot
                ),
                argnums=(0, 1, 2),
            )
        )(q, k, v)

    g_f, g_s = gsum(True), gsum(False)

    def err(a, b):
        return float(np.max(np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))))

    grad_errs = {n: err(a, b) for n, a, b in zip("qkv", g_f, g_s)}
    tol = ATOL + RTOL
    ok = all(e < tol for e in grad_errs.values())
    return {
        "case": f"fused-vs-split:{c['name']}",
        "fwd_max_err": 0.0,  # same forward kernel by construction
        "dq_max_err": round(grad_errs["q"], 5),
        "dk_max_err": round(grad_errs["k"], 5),
        "dv_max_err": round(grad_errs["v"], 5),
        "ok": bool(ok),
    }


def _case_order() -> list[str]:
    order = []
    for c in CASES:
        order += [c["name"], f"fused-vs-split:{c['name']}"]
    return order


def merge_rows(new_rows: list[dict], old_payload: dict | None) -> list[dict]:
    """Per-row provenance merge (round 20): committed rows without a
    ``device``/``mode`` tag inherit the committed payload's header (the
    round-2 record predates per-row tags); a new row replaces the
    committed one UNLESS that would downgrade a Mosaic row to an
    interpreter rerun — the on-chip proof is the scarce artifact, an
    off-chip regeneration must never erase it. Rows are ordered by the
    current case list, unknown (retired) committed cases trail."""
    merged: dict[str, dict] = {}
    if old_payload:
        old_mode = (
            "Mosaic" if old_payload.get("backend") == "tpu"
            else "interpreter"
        )
        for r in old_payload.get("rows", []):
            r = dict(r)
            r.setdefault("device", old_payload.get("device", "?"))
            r.setdefault("mode", old_mode)
            merged[r["case"]] = r
    for r in new_rows:
        prev = merged.get(r["case"])
        if (
            prev is not None
            and prev.get("mode") == "Mosaic"
            and r.get("mode") != "Mosaic"
        ):
            continue
        merged[r["case"]] = r
    order = {name: i for i, name in enumerate(_case_order())}
    return sorted(
        merged.values(),
        key=lambda r: (order.get(r["case"], len(order)), r["case"]),
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--write-docs", action="store_true")
    ap.add_argument("--cases", nargs="+", default=None)
    args = ap.parse_args(argv)
    known = {c["name"] for c in CASES}
    if args.cases:
        unknown = set(args.cases) - known
        if unknown:
            # A typo must not yield a vacuously-green (empty) record.
            ap.error(
                f"unknown case(s) {sorted(unknown)}; known: {sorted(known)}"
            )
    device = jax.devices()[0].device_kind
    backend = jax.default_backend()
    mode = "Mosaic" if backend == "tpu" else "interpreter"
    rows = []
    for c in CASES:
        if args.cases and c["name"] not in args.cases:
            continue
        for runner, label in ((run_case, c["name"]),
                              (run_fused_split_case, f"fused-vs-split:{c['name']}")):
            try:
                rows.append(runner(c))
            except Exception as exc:  # noqa: BLE001
                rows.append(
                    {"case": label, "ok": False,
                     "error": f"{type(exc).__name__}: {exc}"[:200]}
                )
    for r in rows:
        r["device"] = device
        r["mode"] = mode
    header = f"device: {device}  backend: {backend}  mode: {mode}"
    print(header)

    def _table(rs):
        cols = ["case", "fwd", "dq", "dk", "dv", "device", "ok"]
        lines = ["| " + " | ".join(cols) + " |", "|" + "---|" * len(cols)]
        for r in rs:
            dev = f"{r.get('device', '?')} ({r.get('mode', '?')})"
            if "error" in r:
                lines.append(
                    f"| {r['case']} | error: {r['error']} |" + " |" * 3
                    + f" {dev} | FAIL |"
                )
                continue
            lines.append(
                f"| {r['case']} | {r['fwd_max_err']} "
                f"| {r['dq_max_err']} | {r['dk_max_err']} "
                f"| {r['dv_max_err']} | {dev} "
                f"| {'PASS' if r['ok'] else 'FAIL'} |"
            )
        return "\n".join(lines)

    print(_table(rows))
    all_ok = bool(rows) and all(r["ok"] for r in rows)
    payload = {
        "rows": rows, "device": device, "backend": backend, "all_ok": all_ok,
    }
    print(json.dumps(payload))
    if args.write_docs:
        root = os.path.abspath(
            os.path.join(os.path.dirname(__file__), "..", "..", "docs", "benchmarks")
        )
        json_path = os.path.join(root, "attention_parity.json")
        old = None
        try:
            with open(json_path) as f:
                old = json.load(f)
        except (OSError, ValueError):
            pass
        rows = merge_rows(rows, old)
        # The RECORD's verdict (merged rows) is the exit code under
        # --write-docs: an interpreter rerun whose cpu rows lose to a
        # committed Mosaic row must not fail a healthy record.
        all_ok = bool(rows) and all(r["ok"] for r in rows)
        payload = {
            "rows": rows,
            "device": device,
            "backend": backend,
            "all_ok": all_ok,
        }
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=1)
        with open(os.path.join(root, "attention_parity.md"), "w") as f:
            f.write(
                "# Flash-attention parity record (Mosaic vs dense XLA)\n\n"
                "Generated by `python -m distributed_tensorflow_tpu.tools."
                f"attention_parity --write-docs` — last run {header}.\n"
                "Per-row `device` is the backend that actually ran the "
                "row (merge rule: an\ninterpreter rerun never overwrites "
                "a Mosaic row — kernel PROOFS are the\nMosaic-tagged rows "
                "only; interpreter rows are correctness previews awaiting"
                "\nthe chip rerun). Forward and q/k/v gradient max-abs "
                "errors vs the dense\noracle, bf16 inputs, per feature "
                "(causal/window/banding/GQA/kv_lens/offset).\n\n"
                + _table(rows) + "\n"
            )
        print(f"wrote {root}/attention_parity.md")
    return 0 if all_ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
