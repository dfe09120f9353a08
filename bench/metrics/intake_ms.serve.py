"""Mean gap between consecutive ``step`` spans of the scheduler: the
caller's time between ``ModelZoo.step`` calls, in which it submits the
requests that fell due (and the generator loop sleeps when idle)."""


def read(ctx):
    steps = sorted((ctx.spans or {}).get("step", []), key=lambda s: s[0])
    if len(steps) < 2:
        return None
    gaps = [b[0] - a[1] for a, b in zip(steps, steps[1:])]
    return sum(gaps) / len(gaps) * 1e3
