"""Diffusion programs: edges the window's ticks expanded over the edge
slots their step rounds computed (the ``edges`` and ``edge_slots`` of the
``tick`` spans), in %."""
from bench.program import edge_slot_use_pct


def read(run):
    return edge_slot_use_pct(run)
