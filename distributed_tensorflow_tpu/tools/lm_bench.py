"""On-chip LM training benchmark: throughput (tokens/sec) + MFU per config.

The reference's method was measure-everything-and-publish — every mode has
an s/epoch number in its experiment log (reference README.md:13-15,38-40).
Round 2 built the whole GPT surface and measured none of it (VERDICT
round-2 missing #1); this tool closes that: it times `make_lm_train_step`
on the real chip with two disciplines (utils/sync.py):

- ``steps`` train steps amortized inside ONE compiled dispatch (a
  ``lax.scan`` whose carry is the optimizer state — each step depends on
  the previous params, so nothing hoists), resolving per-step time far
  below the fixed cost of one dispatch;
- a D2H value fetch (the final step's loss) ends every timed region.

MFU = compiled-FLOPs-per-step (XLA's own cost model, via
``tools/cost_analysis.analyze_lm`` — the same program, not a hand
formula) / measured step time / chip peak FLOPs.

Usage::

    python -m distributed_tensorflow_tpu.tools.lm_bench            # full grid
    python -m distributed_tensorflow_tpu.tools.lm_bench --steps 16 \
        --configs gpt-s-L512-xla gpt-s-L512-flash

Prints a markdown table and a one-line JSON summary;
``docs/benchmarks/lm_tpu.md`` + ``lm_tpu.json`` are regenerated from this
tool's output (``--write-docs``).
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import optax
from jax import lax

from distributed_tensorflow_tpu.models.gpt import GPTLM
from distributed_tensorflow_tpu.tools.cost_analysis import _chip_peaks, analyze_lm

# Each entry: model kwargs + batch. Two (L, d, layers) points, and at the
# long-L point the attention-variant axis (xla / flash / flash+window /
# GQA) the round-2 verdict asked to separate.
# Batch sizes chosen to FILL the chip (MFU collapses when per-step matmuls
# are too small to tile the MXU — B=2 toy batches measured 1-2% MFU).
CONFIGS = {
    # short-context point: d=256, 4 layers, L=512
    "gpt-s-L512-xla": dict(
        batch=32,
        model=dict(model_dim=256, num_layers=4, num_heads=8, max_len=512),
    ),
    "gpt-s-L512-flash": dict(
        batch=32,
        model=dict(
            model_dim=256, num_layers=4, num_heads=8, max_len=512,
            attention_impl="flash", flash_min_len=0,
        ),
    ),
    # long-context point: same model at L=2048
    "gpt-s-L2048-xla": dict(
        batch=8,
        model=dict(model_dim=256, num_layers=4, num_heads=8, max_len=2048),
    ),
    "gpt-s-L2048-flash": dict(
        batch=8,
        model=dict(
            model_dim=256, num_layers=4, num_heads=8, max_len=2048,
            attention_impl="flash", flash_min_len=0,
        ),
    ),
    "gpt-s-L2048-flash-W512": dict(
        batch=8,
        model=dict(
            model_dim=256, num_layers=4, num_heads=8, max_len=2048,
            attention_impl="flash", flash_min_len=0, window=512,
        ),
    ),
    "gpt-s-L2048-flash-gqa2": dict(
        batch=8,
        model=dict(
            model_dim=256, num_layers=4, num_heads=8, num_kv_heads=2,
            max_len=2048, attention_impl="flash", flash_min_len=0,
        ),
    ),
    # bigger-model points: d=512 and d=1024 (wider matmuls → real MFU)
    "gpt-m-L1024-flash": dict(
        batch=16,
        model=dict(
            model_dim=512, num_layers=8, num_heads=8, max_len=1024,
            attention_impl="flash", flash_min_len=0,
        ),
    ),
    "gpt-l-L1024-flash": dict(
        batch=8,
        model=dict(
            model_dim=1024, num_layers=8, num_heads=16, max_len=1024,
            attention_impl="flash", flash_min_len=0,
        ),
    ),
    # MXU-sized points (round 5): d=2048 tiles the 128-lane MXU properly;
    # remat=True is required to fit HBM (the unremat'd d=2048/L=2048
    # stash is ~20 GB) and trades recompute the model-FLOPs MFU† column
    # deliberately does not credit. These rows are the measured proof
    # that the toy rows' low MFU was the workload (docs/benchmarks/
    # lm_phases.md has the per-phase breakdown).
    "gpt-xl-L1024-flash-remat": dict(
        batch=16,
        model=dict(
            model_dim=2048, num_layers=4, num_heads=16, max_len=1024,
            attention_impl="flash", remat=True,
        ),
    ),
    "gpt-xl-L2048-flash-remat": dict(
        batch=8,
        model=dict(
            model_dim=2048, num_layers=4, num_heads=16, max_len=2048,
            attention_impl="flash", remat=True,
        ),
    ),
}
_VOCAB = 8192

# Generation (KV-cache decode) configs: one scan-compiled greedy_decode
# dispatch per timing — prefill 256 prompt tokens, decode 256 more. The
# variant axis: full-length cache vs rolling windowed cache (O(W) slots)
# vs GQA (cache at Hkv width, grouped-einsum attend — no repeat).
DECODE_CONFIGS = {
    "decode-full": dict(
        batch=8, prompt=256, max_new=256,
        model=dict(model_dim=256, num_layers=4, num_heads=8, max_len=1024),
    ),
    "decode-window256": dict(
        batch=8, prompt=256, max_new=256,
        model=dict(
            model_dim=256, num_layers=4, num_heads=8, max_len=1024,
            window=256,
        ),
    ),
    "decode-gqa2": dict(
        batch=8, prompt=256, max_new=256,
        model=dict(
            model_dim=256, num_layers=4, num_heads=8, num_kv_heads=2,
            max_len=1024,
        ),
    ),
    "decode-long-full": dict(
        batch=4, prompt=256, max_new=256,
        model=dict(model_dim=256, num_layers=4, num_heads=8, max_len=4096),
    ),
    "decode-long-window256": dict(
        batch=4, prompt=256, max_new=256,
        model=dict(
            model_dim=256, num_layers=4, num_heads=8, max_len=4096,
            window=256,
        ),
    ),
}


def bench_decode(name: str, *, seed: int = 0) -> dict:
    spec = DECODE_CONFIGS[name]
    model = GPTLM(vocab_size=_VOCAB, **spec["model"])
    b, p_len, max_new = spec["batch"], spec["prompt"], spec["max_new"]
    params = model.init(seed=1)
    prompt = jax.random.randint(
        jax.random.key(seed), (b, p_len), 0, _VOCAB, jnp.int32
    )
    # Two-point (utils/sync.two_point_seconds): difference a max_new-token
    # and a short-token decode — cancels the fixed dispatch cost AND the
    # shared prefill, leaving pure per-token decode cost. Fast decodes
    # (windowed, GQA) run tens of µs/token, so one generation's delta sits
    # BELOW the dispatch jitter (a committed record briefly showed
    # a 13x phantom speedup from exactly this); chain `reps_in` full
    # generations per dispatch — each rep's prompt is the previous rep's
    # tail, a genuine dependency XLA cannot CSE — so the differenced span
    # is reps_in·(max_new−short) tokens.
    from distributed_tensorflow_tpu.utils.sync import (
        timed_fetch,
        two_point_seconds,
    )

    short = max_new // 4
    reps_in = 8

    def make_chain(new_tokens):
        @jax.jit
        def chain(pr):
            def body(pr, _):
                out = model.greedy_decode(params, pr, new_tokens)
                return out[:, -p_len:].astype(pr.dtype), None

            pr, _ = lax.scan(body, pr, None, length=reps_in)
            return pr

        return chain

    gen1, gen4 = make_chain(short), make_chain(max_new)

    def timed(fn):
        return lambda: timed_fetch(fn, prompt)[0]

    timed(gen1)(), timed(gen4)()  # compile both
    sec_per_tok = two_point_seconds(
        timed(gen1), timed(gen4), reps_in * (max_new - short), reps=3
    )
    return {
        "config": name,
        "batch": b,
        "prompt": p_len,
        "max_new": max_new,
        "cache_len": model.cache_len,
        "ms_per_token": round(sec_per_tok * 1e3, 3),
        "gen_tokens_per_sec": round(b / sec_per_tok, 1),
    }


def render_decode(rows) -> str:
    cols = ["config", "B", "prompt", "new", "cache", "ms/token", "gen tok/s"]
    out = ["| " + " | ".join(cols) + " |", "|" + "---|" * len(cols)]
    for r in rows:
        if "error" in r:
            out.append(f"| {r['config']} | error: {r['error']} |" + " |" * 5)
            continue
        out.append(
            "| {config} | {batch} | {prompt} | {max_new} | {cache_len} | "
            "{ms_per_token:.2f} | {gen_tokens_per_sec:,.0f} |".format(**r)
        )
    return "\n".join(out)


def bench_config(
    name: str, *, steps: int = 32, lr: float = 1e-3, seed: int = 0,
    ceiling_tflops: float | None = None, model_overrides: dict | None = None,
) -> dict:
    spec = CONFIGS[name]
    # Ad-hoc A/B knobs (round 13: remat="selective", matmul_dtype=...)
    # land on every selected config; main() refuses them with
    # --write-docs so a probe cannot re-anchor the committed record.
    mkw = dict(spec["model"], **(model_overrides or {}))
    model = GPTLM(vocab_size=_VOCAB, **mkw)
    b, l = spec["batch"], model.max_len
    params = model.init(seed=1)
    opt = optax.adam(lr)
    opt_state = opt.init(params)
    tokens = jax.random.randint(
        jax.random.key(seed), (b, l), 0, _VOCAB, jnp.int32
    )

    def make_epoch(length):
        @jax.jit
        def epoch(params, opt_state, tokens):
            def body(carry, _):
                params, opt_state = carry
                loss, grads = jax.value_and_grad(model.loss)(params, tokens)
                updates, opt_state = opt.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
                return (params, opt_state), loss

            (params, opt_state), losses = lax.scan(
                body, (params, opt_state), None, length=length
            )
            return params, opt_state, losses

        return epoch

    # TWO-POINT timing (utils/sync.two_point_seconds): one dispatch+fetch
    # carries a fixed cost; dividing a single chain's wall time by `steps`
    # folds it into every step (the round-3 numbers did exactly this).
    # Difference a 4k-step and a k-step warm dispatch instead; median over
    # reps against jitter.
    e1, e4 = make_epoch(steps), make_epoch(4 * steps)

    from distributed_tensorflow_tpu.utils.sync import (
        timed_fetch,
        two_point_seconds,
    )

    last = {}

    def timed(fn):
        def run():
            dt, out = timed_fetch(fn, params, opt_state, tokens)
            last[fn] = float(out[2][-1])  # after the barrier: losses[-1]
            return dt

        return run

    timed(e1)(), timed(e4)()  # compile + warm (fetch = barrier)
    sec_per_step = two_point_seconds(
        timed(e1), timed(e4), 3 * steps, reps=3
    )
    # The loss after exactly `steps` steps (e1's chain) — the field's
    # meaning must track steps_per_dispatch, not the 4x timing chain.
    final_loss = last[e1]
    dt = sec_per_step * steps

    step_ms = sec_per_step * 1e3
    tokens_per_sec = b * l * steps / dt
    row = {
        "config": name,
        "batch": b,
        "seq_len": l,
        "steps_per_dispatch": steps,
        # Measurement provenance — carried-forward rows in a chunked
        # regeneration keep their own method/steps (see --write-docs).
        "timing": f"two-point d({4 * steps}-{steps})x3",
        "step_ms": round(step_ms, 3),
        "tokens_per_sec": round(tokens_per_sec, 1),
        "final_loss": round(final_loss, 4),
    }
    # MFU from the XLA cost model of the SAME single step program.
    report = analyze_lm(model, batch_size=b, optimizer=opt)
    row["flops_per_step"] = report["flops_per_step"]
    row["param_count"] = report["param_count"]
    # Model FLOPs (the scaling-book 6·N·P convention): what the MODEL
    # mathematically requires per step — counts remat recompute as zero
    # and undercounts attention, so MFU† is the conservative utilization
    # the field quotes; the XLA-counted column reflects the compiled
    # program's own op count. N EXCLUDES the embedding and position
    # tables (the Kaplan/Chinchilla reading: lookups and adds do not pay
    # the per-token 2N matmul FLOPs the 6N derivation counts; the tied
    # LM head shares the embedding table). Round 5 used total params,
    # which at vocab 8192/d=256 inflated MFU† by the table's 39% share
    # (ADVICE round 5); lm_tpu.json keeps both counts.
    row["param_count_nonembed"] = report["param_count"] - int(
        params.embed.size + params.pos.size
    )
    row["model_flops_per_step"] = 6 * row["param_count_nonembed"] * b * l
    peaks = _chip_peaks(jax.devices()[0])
    if peaks and report["flops_per_step"]:
        achieved = report["flops_per_step"] / sec_per_step
        row["mfu_pct"] = round(100 * achieved / peaks["flops"], 2)
        # MFU* — against the MEASURED bf16 ceiling (tools/roofline_bench),
        # not the spec sheet: 100% means the step saturates what this
        # chip actually sustains on pure matmul chains.
        if ceiling_tflops:
            row["mfu_star_pct"] = round(
                100 * achieved / (ceiling_tflops * 1e12), 2
            )
            row["mfu_model_pct"] = round(
                100
                * row["model_flops_per_step"]
                / sec_per_step
                / (ceiling_tflops * 1e12),
                2,
            )
        else:
            row["mfu_star_pct"] = None
            row["mfu_model_pct"] = None
    else:
        row["mfu_pct"] = None
        row["mfu_star_pct"] = None
        row["mfu_model_pct"] = None
    return row


def merge_rows(new, old, order):
    """Carry-forward merge for chunked --write-docs regeneration (shared
    with tools/lm_phase_bench): keep previously committed good rows for
    configs not re-measured this run; an error row never displaces a
    previously good measurement."""
    old_good = {r["config"]: r for r in old if "error" not in r}
    new_good = {r["config"] for r in new if "error" not in r}
    out = [
        r for r in new if "error" not in r or r["config"] not in old_good
    ] + [r for c, r in old_good.items() if c not in new_good]
    out.sort(
        key=lambda r: order.index(r["config"])
        if r.get("config") in order
        else len(order)
    )
    return out


def _nonembed_param_count(row) -> int | None:
    """Non-embedding N for a committed row (offline migration of records
    written before round 6): total params minus the d·(vocab + max_len)
    embedding+position tables, derived from the config's model spec."""
    spec = CONFIGS.get(row.get("config"))
    if spec is None or not row.get("param_count"):
        return None
    d = spec["model"]["model_dim"]
    return row["param_count"] - d * (_VOCAB + spec["model"]["max_len"])


def refresh_derived(rows, ceiling, peaks=None) -> None:
    """Recompute every derived column of committed/carried rows from
    their MEASURED fields (step_ms, flops_per_step, param_count): the
    non-embedding N and 6N model FLOPs (round-6 MFU† convention), MFU*
    against the CURRENT ceiling, and — when chip peaks are known — the
    spec-peak MFU. Keeps a chunked regeneration from silently mixing
    denominators, and lets ``--recompute-docs`` migrate the record
    off-chip (no re-measurement)."""
    for r in rows:
        if "error" in r or not r.get("flops_per_step"):
            continue
        achieved = r["flops_per_step"] / (r["step_ms"] / 1e3)
        if "param_count_nonembed" not in r:
            ne = _nonembed_param_count(r)
            if ne is not None:
                r["param_count_nonembed"] = ne
        n_eff = r.get("param_count_nonembed") or r.get("param_count")
        if n_eff:
            r["model_flops_per_step"] = 6 * n_eff * r["batch"] * r["seq_len"]
        if ceiling:
            r["mfu_star_pct"] = round(100 * achieved / (ceiling * 1e12), 2)
            if r.get("model_flops_per_step"):
                r["mfu_model_pct"] = round(
                    100
                    * r["model_flops_per_step"]
                    / (r["step_ms"] / 1e3)
                    / (ceiling * 1e12),
                    2,
                )
        if peaks and peaks.get("flops"):
            r["mfu_pct"] = round(100 * achieved / peaks["flops"], 2)


def run(
    configs=None, *, steps: int = 32, ceiling_tflops=None,
    model_overrides: dict | None = None,
) -> list[dict]:
    rows = []
    for name in configs or CONFIGS:
        try:
            rows.append(
                bench_config(
                    name, steps=steps, ceiling_tflops=ceiling_tflops,
                    model_overrides=model_overrides,
                )
            )
        except Exception as exc:  # noqa: BLE001 — record, keep sweeping
            rows.append(
                {"config": name, "error": f"{type(exc).__name__}: {exc}"[:200]}
            )
    return rows


def render(rows) -> str:
    cols = [
        "config", "B", "L", "step (ms)", "tokens/s", "MFU %", "MFU* %",
        "MFU† %", "params",
    ]
    out = ["| " + " | ".join(cols) + " |", "|" + "---|" * len(cols)]
    for r in rows:
        if "error" in r:
            out.append(f"| {r['config']} | error: {r['error']} |" + " |" * 7)
            continue
        fmt = lambda v: ("%.1f" % v) if v is not None else "—"  # noqa: E731
        out.append(
            "| {config} | {batch} | {seq_len} | {step_ms:.2f} | "
            "{tokens_per_sec:,.0f} | {mfu} | {mfu_star} | {mfu_model} | "
            "{param_count:,} |".format(
                mfu=fmt(r["mfu_pct"]),
                mfu_star=fmt(r.get("mfu_star_pct")),
                mfu_model=fmt(r.get("mfu_model_pct")),
                **r,
            )
        )
    return "\n".join(out)


def emit_bench_events(rows, device: str, events_path: str) -> list[dict]:
    """The measured LM rows as ``bench_point`` journal events (round 10):
    one event per config carrying tokens/s and the MFU columns, so the
    docs tables and the journal share one source
    (``tools/perf_record.py --journal`` reads them back)."""
    from distributed_tensorflow_tpu.observability.journal import EventJournal

    j = EventJournal(events_path, run_id="lm_bench")
    try:
        out = []
        for r in rows:
            if "error" in r or "tokens_per_sec" not in r:
                continue
            out.append(
                j.emit(
                    "bench_point",
                    tool="lm_bench",
                    name=r["config"],
                    value=r["tokens_per_sec"],
                    unit="tokens/s",
                    device=device,
                    step_ms=r.get("step_ms"),
                    mfu_model_pct=r.get("mfu_model_pct"),
                    mfu_star_pct=r.get("mfu_star_pct"),
                )
            )
        return out
    finally:
        j.close()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--configs", nargs="+", default=None, choices=sorted(CONFIGS))
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument(
        "--write-docs",
        action="store_true",
        help="regenerate docs/benchmarks/lm_tpu.{md,json}",
    )
    ap.add_argument(
        "--decode",
        action="store_true",
        help="also run the KV-cache generation configs",
    )
    ap.add_argument(
        "--ceiling-tflops",
        type=float,
        default=None,
        help="measured bf16 ceiling for the MFU* column — measure it in "
        "the same session (tools/roofline_bench); without it the column "
        "is dashed. --recompute-docs defaults to the ceiling the record "
        "itself was derived against",
    )
    ap.add_argument(
        "--recompute-docs",
        action="store_true",
        help="no measurement: reload docs/benchmarks/lm_tpu.json and "
        "recompute every derived column (non-embedding 6N model FLOPs, "
        "MFU*/MFU† vs the current ceiling) from the committed measured "
        "fields, then rewrite md+json — runs anywhere, no chip needed",
    )
    ap.add_argument(
        "--events",
        default=None,
        help="append the measured rows as bench_point journal events "
        "(default with --write-docs: docs/benchmarks/events.jsonl)",
    )
    ap.add_argument(
        "--remat",
        choices=("plain", "selective"),
        default=None,
        help="override every selected config's remat mode (A/B the "
        "round-13 selective policy at the committed shapes); refused "
        "with --write-docs",
    )
    ap.add_argument(
        "--matmul-dtype",
        choices=("int8", "fp8"),
        default=None,
        help="run with quantized projection matmuls (GPTLM "
        "matmul_dtype); refused with --write-docs",
    )
    args = ap.parse_args(argv)
    if (args.remat or args.matmul_dtype) and (args.write_docs or args.events):
        # Probes must touch neither the committed docs nor the gate's
        # bench_point series (their keys carry no override tag — probe
        # points would contaminate the default-config band).
        ap.error(
            "--remat/--matmul-dtype are ad-hoc probes; the committed "
            "record and the gate's event series track the configs as "
            "written (drop --write-docs/--events)"
        )
    ceiling = args.ceiling_tflops
    root = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "..", "docs", "benchmarks")
    )
    json_path = os.path.join(root, "lm_tpu.json")
    if args.recompute_docs:
        with open(json_path) as f:
            payload = json.load(f)
        ceiling = ceiling or payload.get("ceiling_tflops")
        payload["ceiling_tflops"] = ceiling
        refresh_derived(payload["rows"], ceiling)
        table = render(payload["rows"])
        print(table)
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=1)
        _write_md(
            root,
            table,
            payload.get("decode_rows", []),
            ceiling,
            payload.get("device", "TPU v5 lite"),
            "--recompute-docs",
        )
        print(f"recomputed {root}/lm_tpu.md and lm_tpu.json (no re-measurement)")
        return
    overrides = {}
    if args.remat:
        # "plain" is the checkpoint that keeps nothing: GPTLM reads True
        # as "selective" (PR 31), which would make this A/B one program.
        overrides["remat"] = (
            jax.checkpoint_policies.nothing_saveable
            if args.remat == "plain" else "selective"
        )
    if args.matmul_dtype:
        overrides["matmul_dtype"] = args.matmul_dtype
    rows = run(
        args.configs, steps=args.steps, ceiling_tflops=ceiling,
        model_overrides=overrides or None,
    )
    # Journal events carry only THIS run's measurements — the carry-
    # forward merge below folds committed rows from other devices/dates
    # into payload["rows"], which must not be re-stamped as fresh points.
    measured_rows = list(rows)
    device = jax.devices()[0].device_kind
    print(
        f"device: {device}  steps/dispatch: {args.steps}  measured "
        f"ceiling: {f'{ceiling} TFLOPS' if ceiling else 'none (run roofline_bench)'}"
    )
    table = render(rows)
    print(table)
    decode_rows = []
    if args.decode:
        for name in DECODE_CONFIGS:
            try:
                decode_rows.append(bench_decode(name))
            except Exception as exc:  # noqa: BLE001
                decode_rows.append(
                    {"config": name,
                     "error": f"{type(exc).__name__}: {exc}"[:200]}
                )
        print(render_decode(decode_rows))
    payload = {
        "rows": rows, "decode_rows": decode_rows, "device": device,
        "backend": jax.default_backend(), "ceiling_tflops": ceiling,
    }
    print(json.dumps(payload))
    if args.write_docs:
        if os.path.exists(json_path):
            # Partial regeneration (a --configs subset, or no --decode)
            # must not erase the rest of the record: carry forward prior
            # rows for configs not re-measured this run. The full sweep
            # exceeds one chip session's budget, so the record is
            # routinely rebuilt in chunks. Error rows never displace a
            # previously committed good measurement — a transient
            # failure during a touch-up run must not erase the record —
            # and an unreadable prior record REFUSES to overwrite (a
            # truncated json from an interrupted write would otherwise
            # silently drop every config not re-measured this run).
            try:
                with open(json_path) as f:
                    prev = json.load(f)
            except Exception as exc:
                print(
                    f"REFUSING to write docs: existing {json_path} is "
                    f"unreadable ({type(exc).__name__}: {exc}) and a "
                    "partial run would erase its other configs; move it "
                    "aside to regenerate from scratch"
                )
                return

            rows = merge_rows(rows, prev.get("rows", []), list(CONFIGS))
            # Carried rows keep their measured times but every derived
            # column tracks the CURRENT conventions (non-embedding 6N,
            # current ceiling) — a roofline re-measure or a denominator
            # fix must not leave the table silently mixing conventions.
            refresh_derived(rows, ceiling, _chip_peaks(jax.devices()[0]) or {})
            payload["rows"] = rows
            table = render(rows)
            decode_rows = merge_rows(
                decode_rows, prev.get("decode_rows", []),
                list(DECODE_CONFIGS),
            )
            payload["decode_rows"] = decode_rows
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=1)
        cmd_flags = f"--steps {args.steps}" + (" --decode" if args.decode else "")
        _write_md(root, table, decode_rows, ceiling, device, cmd_flags)
        print(f"wrote {root}/lm_tpu.md and lm_tpu.json")
    events_path = args.events
    if events_path is None and args.write_docs:
        events_path = os.path.join(root, "events.jsonl")
    if events_path:
        n = len(emit_bench_events(measured_rows, device, events_path))
        print(f"appended {n} bench_point events to {events_path}")


def _write_md(root, table, decode_rows, ceiling, device, cmd_flags) -> None:
    with open(os.path.join(root, "lm_tpu.md"), "w") as f:
        f.write(
            "# LM training on one TPU chip\n\n"
            f"Generated by `python -m distributed_tensorflow_tpu.tools."
            f"lm_bench {cmd_flags} --write-docs` on {device} "
            "(bf16 matmuls, adam, vocab 8192; two-point timing — per "
            "row, step time is the Δ between a 4k- and a k-step warm "
            "dispatch over 3k with D2H-fetch barriers, k and the "
            "method recorded per row in lm_tpu.json `timing` — rows "
            "may come from different chunked runs; MFU = XLA-counted "
            "FLOPs / measured step time / v5e spec peak"
            + (
                ", MFU* = the same against the MEASURED bf16 ceiling "
                f"({ceiling} TFLOPS, docs/benchmarks/roofline_tpu.md), "
                "MFU† = model FLOPs (6·N·tokens, the scaling-book "
                "convention — credits no remat recompute; N EXCLUDES "
                "the embedding/position tables, whose lookups pay no "
                "per-token matmul FLOPs — the tied head shares the "
                "embedding; both N's are in lm_tpu.json) over the "
                "measured ceiling"
                if ceiling
                else "; MFU* is dashed — no measured roofline record; "
                "run tools/roofline_bench --write-docs first"
            )
            + ". The `params` column is total parameters.\n\n" + table + "\n\n"
            + (
                "## Generation (KV-cache greedy decode, one compiled "
                "scan)\n\n" + render_decode(decode_rows) + "\n\n"
                "Decode config gaps now track their KV-cache traffic "
                "ratios (full:gqa2 = 4× cache → ~2.3× time; the "
                "balance is shared weight/embedding reads). The "
                "round-4 record showed decode-full 15× gqa2 — that "
                "was the layer `lax.scan` double-buffering the whole "
                "stacked cache every token (xs→ys copies); "
                "`GPTLM.decode_step` now unrolls the layer loop "
                "(939→306 µs/token at c=1024, 2311→191 at c=4096 in "
                "the isolation benches; decode graphs are tiny, so "
                "compile time is unaffected).\n\n"
                if decode_rows
                else ""
            )
            + "Provenance: every row here was measured before this round on another installation; not re-measured. "
            "Reading the MFU columns: the roofline of the same "
            "record (roofline_tpu.md) showed that chip sustaining "
            "~98% of spec peak on pure matmul chains — the round-3 "
            "claim that 'the environment pins MFU at 1-2.5%' was a "
            "measurement artifact (one dispatch+fetch's fixed cost "
            "was being divided into every step; the two-point "
            "method cancels it). What remains between "
            "these MFU* numbers and 100% is the WORKLOAD: toy "
            "widths (d=256-1024 matmuls tile the MXU poorly next "
            "to the roofline's 4096² chains), attention/layernorm/"
            "loss bandwidth-bound phases, and per-step optimizer "
            "traffic. Compare configs against each other AND "
            "against MFU*=100 — both comparisons are now "
            "meaningful. (Round 6: MFU† switched its N from total to "
            "non-embedding parameters — the scaling-book reading; at "
            "d=256 the 8192-entry table was 39% of N, so those rows' "
            "MFU† dropped by roughly that fraction. Step times are "
            "unchanged.)\n"
        )


if __name__ == "__main__":
    main()
