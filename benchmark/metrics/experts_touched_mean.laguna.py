"""Distinct routed experts a decode step's tokens chose, per expert layer,
averaged over the window's decode steps: the program's own count, summed
over each chunk's steps and carried on its ``decode_chunk`` span
(``experts_touched``). None where the spans carry no such count."""
from benchmark.lib import flops_laguna


def read(run):
    return flops_laguna.experts_touched_mean(run)
