"""Closed loop at saturation: ``backlog_per_slot`` clips per slot wait
before every tick, so every tick runs every slot."""
from bench import generator, loop


def drive(fleet, pool, traffic: dict, seconds: float, seed: int, *,
          slots: int, drain_s: float, overloaded, **kw):
    backlog = traffic["backlog_per_slot"] * slots
    order = generator.pool_order(len(pool), 1 << 20, seed)
    return loop.closed_loop(fleet, order, pool, seconds, drain_s, backlog,
                            overloaded, **kw)
