"""Open loop: clips due on a Poisson schedule at ``rate_clips_per_s``,
whatever the fleet does; latency runs from each clip's due time."""
from bench import generator, loop


def drive(fleet, pool, traffic: dict, seconds: float, seed: int, *,
          slots: int, drain_s: float, overloaded, **kw):
    due = generator.arrival_schedule(traffic["rate_clips_per_s"], seconds,
                                     seed)
    order = generator.pool_order(len(pool), len(due), seed)
    return loop.open_loop(fleet, due, order, pool, seconds, drain_s,
                          overloaded, **kw)
