"""Whole step: forward operations of the tokens the model ran for the
requests that finished in the window (prefilled behind any cached
prefix, decoded, and the head for each picked token) / (window x peak)."""
from benchmark.lib import flops


def read(run):
    c = run.counters
    if not c.get("tokens_run"):
        return None
    ops = flops.serve_flops(run.cfg, c["tokens_run"], c["keys_seen"], c["logits_rows"])
    return 100.0 * ops / (run.window_s * run.chips * run.peaks["bf16_flops_per_s"])
