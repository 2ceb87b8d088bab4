"""Measured per-phase decomposition of the LM train step (round 5).

Round 4 closed with MFU* at 2.4-5.2% and an *argued* explanation ("toy
widths, bandwidth-bound phases, optimizer traffic") — this tool measures
it. Each phase is a chained-scan region timed with the two-point
discipline (utils/sync.two_point_seconds; CLAUDE.md timing traps), and
the phases nest so differences isolate stages:

- ``blocks-fwd``  — embed + the transformer stack, no logits/loss
- ``fwd``         — + final layernorm, logits matmul, masked CE
- ``fwd+bwd``     — value_and_grad of the same loss (params fixed)
- ``step``        — + adam update (the real train step)

so ``logits+loss = fwd − blocks-fwd``, ``backward = fwd+bwd − fwd``,
``optimizer = step − fwd+bwd``. Two microbenches split the block cost:
``attn`` (the model's attention op at its exact shapes) and ``ffn`` (the
block's two FFN matmuls), each chained output→input.

Every chained region feeds a data-dependent perturbation of the tokens
(derived from the previous iteration's loss) so XLA cannot hoist the
loop-invariant computation out of the scan — without it, a fwd-only
region measures one application plus a scalar loop (cost a debugging
cycle; the training regions chain through params naturally).

Usage::

    python -m distributed_tensorflow_tpu.tools.lm_phase_bench            # default grid
    python -m distributed_tensorflow_tpu.tools.lm_phase_bench --write-docs

Writes docs/benchmarks/lm_phases.md + .json with ``--write-docs``.
"""

from __future__ import annotations

import argparse
import json
import os

import jax
import jax.numpy as jnp
import optax
from jax import lax

from distributed_tensorflow_tpu.models.gpt import GPTLM, _ce_from_logits
from distributed_tensorflow_tpu.utils.sync import timed_fetch, two_point_seconds

_VOCAB = 8192

# (name, model kwargs, batch): one toy row from the round-4 table and the
# MXU-sized rows the round-5 push added. remat=True on the big rows —
# required to fit HBM (the d=2048/L=2048 stash is ~20 GB unremat'd) and
# part of what the measurement must therefore attribute.
CONFIGS = {
    "gpt-s-L512": (
        dict(model_dim=256, num_layers=4, num_heads=8, max_len=512), 32
    ),
    "gpt-l-L1024": (
        dict(
            model_dim=1024, num_layers=8, num_heads=16, max_len=1024,
            attention_impl="flash", flash_min_len=0,
        ),
        8,
    ),
    "gpt-xl-L1024": (
        dict(
            model_dim=2048, num_layers=4, num_heads=16, max_len=1024,
            attention_impl="flash", remat=True,
        ),
        16,
    ),
    "gpt-xl-L2048": (
        dict(
            model_dim=2048, num_layers=4, num_heads=16, max_len=2048,
            attention_impl="flash", remat=True,
        ),
        8,
    ),
    # CPU-runnable flash+remat row (round 13): small enough for the
    # Pallas interpreter, so the remat-policy comparison region has a
    # committed point on an egress-less container; its numbers are
    # interpreter-scale (the row is device-tagged and the table marks
    # it) — the chip rerun replaces them with Mosaic measurements.
    "gpt-tiny-L128-flash-remat": (
        dict(
            model_dim=128, num_layers=2, num_heads=4, max_len=128,
            attention_impl="flash", flash_min_len=0, remat=True,
        ),
        4,
    ),
}


def _perturb(tokens, seed_scalar):
    """Data-dependent token rotation: mixes a scalar derived from the
    previous iteration's output into every position, mod vocab — cheap,
    and makes each iteration's forward depend on the last (no hoisting)."""
    shift = jnp.abs(jnp.nan_to_num(seed_scalar * 1e6)).astype(jnp.int32) % 7
    return (tokens + shift) % _VOCAB


def _chain(body, n):
    """Scan ``body(params, tokens) -> scalar`` n times, tokens perturbed
    by each iteration's scalar result. ``params`` is a RUNTIME argument —
    closing over it would bake the whole parameter tree into the HLO as
    literals, and a 220M-param tree makes an ~880 MB compile payload
    (cost a debugging cycle)."""

    @jax.jit
    def run(params, tokens):
        def step(carry, _):
            toks, acc = carry
            out = body(params, toks)
            return (_perturb(toks, out), acc + out), ()

        (toks, acc), _ = lax.scan(step, (tokens, 0.0), None, length=n)
        return acc

    return run


def _region_seconds(make_run, args, steps, reps):
    r1, r4 = make_run(steps), make_run(4 * steps)
    t1 = lambda: timed_fetch(r1, *args)[0]  # noqa: E731
    t4 = lambda: timed_fetch(r4, *args)[0]  # noqa: E731
    t1(), t4()  # compile + warm
    return two_point_seconds(t1, t4, 3 * steps, reps=reps)


def bench_phases(
    name: str, *, steps: int = 4, reps: int = 3,
    ceiling_tflops: float | None = None, matmul_dtype: str | None = None,
) -> dict:
    mkw, b = CONFIGS[name]
    if matmul_dtype:
        mkw = dict(mkw, matmul_dtype=matmul_dtype)
    if mkw.get("remat") is True:
        # The rows' own backward stays the checkpoint that keeps nothing
        # (what remat=True meant when they were first measured), so that
        # "backward" against "backward-selective" is still an A/B:
        # since PR 31 GPTLM reads True as "selective".
        mkw = dict(mkw, remat=jax.checkpoint_policies.nothing_saveable)
    model = GPTLM(vocab_size=_VOCAB, **mkw)
    params = model.init(seed=1)
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)
    tokens = jax.random.randint(
        jax.random.key(0), (b, model.max_len), 0, _VOCAB, jnp.int32
    )
    l = model.max_len

    def blocks_fwd(p, toks):
        h = model._embed_tokens(p, toks, jnp.arange(l))

        def body(h, blk):
            h, _, _ = model._block(blk, h, positions=jnp.arange(l))
            return h, ()

        body = model._remat_wrap(body)  # honors the policy knob too
        h, _ = lax.scan(body, h, p.blocks)
        return jnp.sum(h.astype(jnp.float32)) * 1e-9

    def fwd(p, toks):
        return model.loss(p, toks)

    def fwd_bwd(p, toks):
        loss, grads = jax.value_and_grad(model.loss)(p, toks)
        # Fold a hair of every grad into the scalar so the backward is
        # demanded (loss alone depends only on the forward).
        gsum = sum(
            jnp.sum(g.astype(jnp.float32)) for g in jax.tree.leaves(grads)
        )
        return loss + gsum * 1e-30

    def fwd_dgrad(p, toks):
        # The dgrad-only cut (round 9, VERDICT r5 weak #4): differentiate
        # wrt the block-stack INPUT h0 with the params held constant —
        # the backward sweeps the same layer chain (and, under remat,
        # does the same per-layer recompute) but every wgrad matmul is
        # dead code XLA drops. fwd+bwd − this = the wgrad matmuls;
        # this − fwd = dgrad (+ recompute when remat).
        positions = jnp.arange(l)

        def loss_from_h(h):
            def body(h, blk):
                h, _, _ = model._block(blk, h, positions=positions)
                return h, ()

            b2 = model._remat_wrap(body)
            h, _ = lax.scan(b2, h, p.blocks)
            logits = model._logits(p, h)
            return _ce_from_logits(logits, toks)

        h0 = model._embed_tokens(p, toks, positions)
        loss, gh = jax.value_and_grad(loss_from_h)(h0)
        return loss + jnp.sum(gh.astype(jnp.float32)) * 1e-30

    sec = {}
    for key, body in [
        ("blocks-fwd", blocks_fwd),
        ("fwd", fwd),
        ("fwd+bwd", fwd_bwd),
        ("fwd+dgrad", fwd_dgrad),
    ]:
        sec[key] = _region_seconds(
            lambda n, body=body: _chain(body, n),
            (params, tokens),
            steps,
            reps,
        )

    # Remat-policy comparison region (round 13, ROADMAP item 4): the same
    # fwd+bwd region under remat="selective" (flash out+lse saved, only
    # the LN/QKV/MLP half replayed) — measured on remat rows, where the
    # two policies are the actual A/B. Params as runtime args (the
    # HTTP-413 gotcha) ride in through _chain unchanged.
    if model.remat:
        sel_model = GPTLM(
            vocab_size=_VOCAB, **dict(mkw, remat="selective")
        )

        def fwd_bwd_sel(p, toks):
            loss, grads = jax.value_and_grad(sel_model.loss)(p, toks)
            gsum = sum(
                jnp.sum(g.astype(jnp.float32))
                for g in jax.tree.leaves(grads)
            )
            return loss + gsum * 1e-30

        sec["fwd+bwd-selective"] = _region_seconds(
            lambda n: _chain(fwd_bwd_sel, n), (params, tokens), steps, reps
        )

    # Full train step: chained through (params, opt_state) — the same
    # region lm_bench times.
    def make_step_run(n):
        @jax.jit
        def run(params, opt_state, tokens):
            def body(carry, _):
                p, o = carry
                loss, grads = jax.value_and_grad(model.loss)(p, tokens)
                updates, o = opt.update(grads, o, p)
                p = optax.apply_updates(p, updates)
                return (p, o), loss

            (_, _), losses = lax.scan(
                body, (params, opt_state), None, length=n
            )
            return losses[-1]

        return run

    sec["step"] = _region_seconds(
        make_step_run, (params, opt_state, tokens), steps, reps
    )

    # Microbench split of the block interior at the model's exact shapes:
    # attention (the op the blocks call) and the FFN pair, chained
    # output->input so nothing hoists.
    h_dim, kv = model.num_heads, model.num_kv_heads
    d, hd = model.model_dim, model.head_dim
    blk0 = jax.tree.map(lambda x: x[0], params.blocks)
    x0 = jax.random.normal(
        jax.random.key(1), (b, l, d), model.compute_dtype
    )

    def attn_once(blk, x):
        q = model._dot(x, blk.wq).reshape(b, l, h_dim, hd)
        k = model._dot(x, blk.wk).reshape(b, l, kv, hd)
        v = model._dot(x, blk.wv).reshape(b, l, kv, hd)
        o = model._attend(q, k, v)
        return model._dot(o.reshape(b, l, d), blk.wo)

    def ffn_once(blk, x):
        out, _ = model._ffn(blk, x)
        return out.astype(model.compute_dtype)

    def micro(body):
        # blk rides as a runtime arg for the same HLO-size reason as
        # params in _chain.
        def make(n):
            @jax.jit
            def run(blk, x):
                def step(x, _):
                    y = body(blk, x)
                    return y.astype(x.dtype), ()

                y, _ = lax.scan(step, x, None, length=n)
                return jnp.sum(y.astype(jnp.float32))

            return run

        r1, r4 = make(steps), make(4 * steps)
        t1 = lambda: timed_fetch(r1, blk0, x0)[0]  # noqa: E731
        t4 = lambda: timed_fetch(r4, blk0, x0)[0]  # noqa: E731
        t1(), t4()
        return two_point_seconds(t1, t4, 3 * steps, reps=reps)

    per_layer_attn = micro(attn_once)
    per_layer_ffn = micro(ffn_once)

    n_params = sum(p.size for p in jax.tree.leaves(params))
    # 6N model FLOPs with N excluding the embedding/position tables (the
    # Kaplan/Chinchilla convention — lookups pay no per-token matmul
    # FLOPs; the tied head shares the embedding). Round 5 used total
    # params, inflating the toy rows' MFU† by the table's share
    # (ADVICE round 5; lm_bench.py carries the same fix).
    n_nonembed = int(n_params - params.embed.size - params.pos.size)
    toks_per_step = b * l
    model_flops = 6 * n_nonembed * toks_per_step
    row = {
        "config": name,
        "batch": b,
        "seq_len": l,
        "param_count": int(n_params),
        "param_count_nonembed": n_nonembed,
        "remat": bool(model.remat),
        "matmul_dtype": model.matmul_dtype,
        "device": jax.devices()[0].device_kind,
        "phase_ms": {
            "blocks-fwd": round(sec["blocks-fwd"] * 1e3, 2),
            "logits+loss": round((sec["fwd"] - sec["blocks-fwd"]) * 1e3, 2),
            "backward": round((sec["fwd+bwd"] - sec["fwd"]) * 1e3, 2),
            # The round-13 comparison column: the same backward under the
            # selective policy (None on non-remat rows and rows measured
            # before the region existed — rendered as an em-dash).
            "backward-selective": (
                round((sec["fwd+bwd-selective"] - sec["fwd"]) * 1e3, 2)
                if "fwd+bwd-selective" in sec
                else None
            ),
            "bwd-dgrad": round((sec["fwd+dgrad"] - sec["fwd"]) * 1e3, 2),
            "optimizer": round((sec["step"] - sec["fwd+bwd"]) * 1e3, 2),
            "step": round(sec["step"] * 1e3, 2),
        },
        "per_layer_ms": {
            "attention": round(per_layer_attn * 1e3, 3),
            "ffn": round(per_layer_ffn * 1e3, 3),
            "layers": model.num_layers,
        },
        "tokens_per_sec": round(toks_per_step / sec["step"], 1),
        "model_flops_per_step": model_flops,
    }
    row["backward_split"] = _backward_split(row["phase_ms"], model.remat)
    # MFU† against a MEASURED ceiling (--ceiling-tflops, from the same
    # session's roofline) — never hardcoded, recorded with the row.
    if ceiling_tflops:
        row["ceiling_tflops"] = ceiling_tflops
        row["mfu_model_pct"] = round(
            100 * model_flops / sec["step"] / (ceiling_tflops * 1e12), 2
        )
    else:
        row["ceiling_tflops"] = None
        row["mfu_model_pct"] = None
    return row


def _backward_split(phase_ms: dict, remat: bool) -> dict | None:
    """Decompose the backward lump (VERDICT r5 weak #4):
    ``backward = recompute + dgrad + wgrad``, where recompute (remat rows)
    is one blocks-forward replay — attributed at the measured
    ``blocks-fwd`` time, since jax.checkpoint replays exactly that scan —
    and the measured ``bwd-dgrad`` region is dgrad(+recompute) with the
    wgrad matmuls dead-coded away. None for rows measured before the
    dgrad region existed (they render an em-dash until the next chip
    run)."""
    dg = phase_ms.get("bwd-dgrad")
    if dg is None:
        return None
    rec = phase_ms["blocks-fwd"] if remat else 0.0
    return {
        "recompute": round(rec, 2),
        "dgrad": round(dg - rec, 2),
        "wgrad": round(phase_ms["backward"] - dg, 2),
    }


def _nonembed_param_count(row) -> int | None:
    """Non-embedding N for a committed row (offline migration of records
    written before round 6): total minus the d·(vocab + max_len) tables."""
    if row.get("config") not in CONFIGS or not row.get("param_count"):
        return None
    mkw, _ = CONFIGS[row["config"]]
    return row["param_count"] - mkw["model_dim"] * (_VOCAB + mkw["max_len"])


def refresh_derived(rows, ceiling) -> None:
    """Recompute the derived columns (non-embedding 6N model FLOPs, MFU†
    vs the current ceiling) of committed/carried rows from their measured
    fields — shared by the carry-forward merge and ``--recompute-docs``."""
    for r in rows:
        if "error" in r or not r.get("phase_ms"):
            continue
        r["backward_split"] = _backward_split(
            r["phase_ms"], bool(r.get("remat"))
        )
        if "param_count_nonembed" not in r:
            ne = _nonembed_param_count(r)
            if ne is not None:
                r["param_count_nonembed"] = ne
        n_eff = r.get("param_count_nonembed") or r.get("param_count")
        if n_eff:
            r["model_flops_per_step"] = 6 * n_eff * r["batch"] * r["seq_len"]
        if ceiling and r.get("model_flops_per_step"):
            r["ceiling_tflops"] = ceiling
            r["mfu_model_pct"] = round(
                100
                * r["model_flops_per_step"]
                / (r["phase_ms"]["step"] / 1e3)
                / (ceiling * 1e12),
                2,
            )


def render(rows) -> str:
    cols = [
        "config", "B", "L", "blocks-fwd", "logits+loss", "backward",
        "bwd selective", "bwd rec/dgrad/wgrad", "optimizer", "step (ms)",
        "attn/layer", "ffn/layer", "MFU†",
    ]
    out = ["| " + " | ".join(cols) + " |", "|" + "---|" * len(cols)]
    for r in rows:
        if "error" in r:
            out.append(
                f"| {r['config']} | error: {r['error']} |" + " |" * 11
            )
            continue
        p, pl = r["phase_ms"], r["per_layer_ms"]
        mfu = r.get("mfu_model_pct")
        split = r.get("backward_split")
        split_s = (
            "—"
            if not split
            else f"{split['recompute']}/{split['dgrad']}/{split['wgrad']}"
        )
        # Provenance mark (serving.md convention): rows measured off-chip
        # carry their device; legacy rows without the key are the TPU
        # record measured before this round on another installation.
        dev = r.get("device")
        cfg = r["config"] + (
            "" if dev is None or "TPU" in str(dev) else f" ({dev})"
        )
        sel = p.get("backward-selective")
        out.append(
            "| {config} | {batch} | {seq_len} | {b} | {ll} | {bw} | {sel} "
            "| {sp} | {opt} | {st} | {at} | {ff} | {mfu} |".format(
                config=cfg, batch=r["batch"], seq_len=r["seq_len"],
                b=p["blocks-fwd"], ll=p["logits+loss"], bw=p["backward"],
                sel="—" if sel is None else sel,
                sp=split_s, opt=p["optimizer"], st=p["step"],
                at=pl["attention"], ff=pl["ffn"],
                mfu="—" if mfu is None else mfu,
            )
        )
    return "\n".join(out)


def emit_bench_events(rows, events_path: str) -> list[dict]:
    """THIS RUN's measured rows as ``bench_point`` journal events, so the
    round-12 regression gate covers the phase series — including the new
    plain-vs-selective backward pair. Series identity is
    ``(lm_phase_bench, <config>/<phase>, device)``: a chip rerun starts
    its own series and never collides with a CPU-container point."""
    from distributed_tensorflow_tpu.observability.journal import EventJournal

    j = EventJournal(events_path, run_id="lm_phase_bench")
    try:
        out = []
        for r in rows:
            if "error" in r or not r.get("phase_ms"):
                continue
            pm = r["phase_ms"]
            common = dict(
                tool="lm_phase_bench",
                device=r.get("device") or "",
                config=r["config"],
            )
            out.append(
                j.emit(
                    "bench_point", name=f"{r['config']}/step_ms",
                    value=pm["step"], unit="ms", **common,
                )
            )
            out.append(
                j.emit(
                    "bench_point", name=f"{r['config']}/backward_ms",
                    value=pm["backward"], unit="ms", **common,
                )
            )
            if pm.get("backward-selective") is not None:
                out.append(
                    j.emit(
                        "bench_point",
                        name=f"{r['config']}/backward_selective_ms",
                        value=pm["backward-selective"], unit="ms", **common,
                    )
                )
        return out
    finally:
        j.close()


def recorded_ceiling(rows) -> float | None:
    """The bf16 ceiling the record's own derived columns were computed
    against (every measured row carries it), or None."""
    return next(
        (r["ceiling_tflops"] for r in rows if r.get("ceiling_tflops")), None
    )


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--configs", nargs="+", default=None, choices=sorted(CONFIGS))
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--write-docs", action="store_true")
    ap.add_argument(
        "--recompute-docs",
        action="store_true",
        help="no measurement: reload docs/benchmarks/lm_phases.json, "
        "recompute the derived columns (non-embedding 6N, MFU† vs the "
        "current ceiling) and rewrite md+json — runs anywhere, no chip",
    )
    ap.add_argument(
        "--ceiling-tflops",
        type=float,
        default=None,
        help="measured bf16 ceiling for the MFU† column — measure it in "
        "the same session (tools/roofline_bench); without it the column "
        "is dashed. --recompute-docs and carried rows default to the "
        "ceiling the record itself was derived against",
    )
    ap.add_argument(
        "--matmul-dtype",
        choices=("int8", "fp8"),
        default=None,
        help="run the selected configs with quantized projection matmuls "
        "(GPTLM matmul_dtype) — an ad-hoc A/B probe, refused with "
        "--write-docs so it cannot silently re-anchor the record",
    )
    ap.add_argument(
        "--events",
        default=None,
        help="append the measured rows as bench_point journal events to "
        "this events.jsonl (default with --write-docs: "
        "docs/benchmarks/events.jsonl — the regression-gate series)",
    )
    args = ap.parse_args(argv)
    if args.matmul_dtype and (args.write_docs or args.events):
        # A probe must touch NEITHER committed surface: not the docs, and
        # not the bench_point journal — its series keys carry no override
        # tag, so probe points would contaminate the regression-gate band
        # for the default-precision record.
        ap.error(
            "--matmul-dtype is an ad-hoc probe; the committed record and "
            "the gate's event series track the default precision (drop "
            "--write-docs/--events)"
        )
    ceiling = args.ceiling_tflops
    root = os.path.abspath(
        os.path.join(
            os.path.dirname(__file__), "..", "..", "docs", "benchmarks"
        )
    )
    json_path = os.path.join(root, "lm_phases.json")
    if args.recompute_docs:
        with open(json_path) as f:
            payload = json.load(f)
        ceiling = ceiling or recorded_ceiling(payload["rows"])
        refresh_derived(payload["rows"], ceiling)
        table = render(payload["rows"])
        print(table)
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=1)
        _write_md(root, table, ceiling)
        print(f"recomputed {root}/lm_phases.md and lm_phases.json")
        return
    rows = []
    for name in args.configs or CONFIGS:
        try:
            rows.append(
                bench_phases(
                    name, steps=args.steps, reps=args.reps,
                    ceiling_tflops=ceiling,
                    matmul_dtype=args.matmul_dtype,
                )
            )
        except Exception as exc:  # noqa: BLE001 — record, keep sweeping
            rows.append(
                {"config": name, "error": f"{type(exc).__name__}: {exc}"[:200]}
            )
        print(json.dumps(rows[-1]))
    measured_rows = list(rows)  # events cover THIS run, not carried rows
    if args.write_docs:
        from distributed_tensorflow_tpu.tools.lm_bench import merge_rows

        prev = None  # the merged prior record, when one was loadable
        if os.path.exists(json_path):
            # Carry-forward merge (lm_bench's --write-docs discipline): a
            # --configs touch-up or a transient error must not
            # erase previously committed rows; an unreadable record
            # refuses to overwrite.
            try:
                with open(json_path) as f:
                    prev = json.load(f)
            except Exception as exc:
                print(
                    f"REFUSING to write docs: existing {json_path} is "
                    f"unreadable ({type(exc).__name__}: {exc}); move it "
                    "aside to regenerate from scratch"
                )
                return
            rows = merge_rows(rows, prev.get("rows", []), list(CONFIGS))
            # Carried rows track the CURRENT conventions (non-embedding
            # 6N, current ceiling — theirs, when this run measured none).
            ceiling = ceiling or recorded_ceiling(rows)
            refresh_derived(rows, ceiling)
        table = render(rows)
        print(table)
        # Top-level device describes the LEGACY rows (measured before
        # per-row device tags); preserve it across merges so a CPU
        # touch-up run cannot relabel the carried TPU rows.
        device = jax.devices()[0].device_kind
        if prev is not None:
            device = prev.get("device", device)
        with open(json_path, "w") as f:
            json.dump({"rows": rows, "device": device}, f, indent=1)
        _write_md(root, table, ceiling)
        print(f"wrote {root}/lm_phases.md and lm_phases.json")
    else:
        print(render(rows))
    events_path = args.events
    if events_path is None and args.write_docs:
        events_path = os.path.join(root, "events.jsonl")
    if events_path:
        n = len(emit_bench_events(measured_rows, events_path))
        print(f"appended {n} bench_point events to {events_path}")


def _write_md(root, table, ceiling) -> None:
    with open(os.path.join(root, "lm_phases.md"), "w") as f:
        f.write(
            "# LM train-step phase decomposition (one TPU v5e chip)\n\n"
            "Generated by `python -m distributed_tensorflow_tpu.tools."
            "lm_phase_bench --write-docs`. Phases nest (see the module "
            "docstring): logits+loss = fwd − blocks-fwd, backward = "
            "fwd+bwd − fwd, optimizer = step − fwd+bwd; attn/ffn are "
            "per-layer forward microbenches at the exact block shapes. "
            "All regions chained scans with data-dependent feeds, "
            "two-point timed. MFU† = 6·N·tokens (the scaling-book "
            "model-FLOPs convention — counts remat recompute as zero; N "
            "EXCLUDES the embedding/position tables, whose lookups pay "
            "no per-token matmul FLOPs — round 6 fixed the denominator, "
            "lm_phases.json keeps both counts) over the MEASURED bf16 "
            f"ceiling ({ceiling} TFLOPS, roofline_tpu.md).\n\n"
            + table
            + "\n\nReading it: the toy rows lose their step time to "
            "phases that are small matmuls and scatters (d=256 tiles "
            "an eighth of the MXU lane width), with the BACKWARD "
            "pass the dominant term. The MXU-sized rows (d=2048, "
            "remat) put ~40% of the measured ceiling into model "
            "FLOPs — the round-3/4 \"MFU gap\" was the WORKLOAD, as "
            "the roofline said, not the environment; their backward "
            "includes one full forward recompute (remat), which "
            "MFU† deliberately does not credit.\n\n"
            "The backward split (round 9): backward = remat RECOMPUTE "
            "(one blocks-forward replay — the measured blocks-fwd "
            "time) + DGRAD (the measured `bwd-dgrad` region minus "
            "recompute; wgrad matmuls dead-coded) + WGRAD (fwd+bwd "
            "minus the dgrad region). On the committed xl rows the "
            "recompute third is 49-58 ms of the 170-189 ms backward "
            "(~30%), leaving ~120-131 ms of dgrad+wgrad — and since "
            "each of recompute/dgrad/wgrad is one forward's worth of "
            "matmul FLOPs (3x blocks-fwd = 147-173 ms, matching the "
            "measured lump), **no single term dominates: the backward "
            "is three near-equal forwards**. The attackable third is "
            "the recompute (a remat policy that stashes cheap "
            "activations), because dgrad+wgrad are irreducible model "
            "FLOPs; the probed dots-saveable policies (CLAUDE.md) "
            "already showed naive stashing LOSES to recompute at these "
            "shapes, so the next step is a selective policy, not less "
            "remat. The rec/dgrad/wgrad column fills from the first "
            "on-chip rerun with the `bwd-dgrad` region (em-dash = "
            "pre-round-9 row).\n\n"
            "The `bwd selective` column (round 13) is that selective "
            "policy, built: the same fwd+bwd region re-measured with "
            "`remat=\"selective\"` — a Pallas-aware jax.checkpoint "
            "policy that SAVES the flash-attention out+lse (O(B·L·d) to "
            "store) so the backward replays only the layernorm/QKV/MLP "
            "half of each block, grad-identical to plain remat "
            "(test_gpt.py) and paired with the fused one-pass dq+dk+dv "
            "backward kernel (ops/pallas_attention, "
            "attention_parity's fused-vs-split rows). Rows tagged with "
            "a device (e.g. `(cpu)`) are off-chip interpreter points "
            "committed so the regression-gate series exists — their "
            "absolute times are NOT comparable to the TPU rows (measured before this round on another installation; not re-measured); "
            "the xl rows' selective column is an em-dash until the chip "
            "rerun regenerates this table (serving.md provenance "
            "convention; no committed MFU† row is re-anchored by the "
            "policy change — `--recompute-docs` migrates derived "
            "columns only).\n"
        )


if __name__ == "__main__":
    main()
