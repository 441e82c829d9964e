"""Stream-timesteps returned to clients per second over the window's whole
ticks, from the first tick's start to the last one's end."""


def read(run):
    if not run.ticks:
        return None
    frames = sum(t.frames for t in run.ticks)
    span = run.ticks[-1].end - run.ticks[0].start
    return frames / span if frames and span > 0 else None
