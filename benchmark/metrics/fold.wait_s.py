"""Mean per beam of the ``fold.fetch`` spans (telemetry.json timers):
the host blocked on the folds of each DM group."""


def read(trace, beams, ctx):
    t = [b["timers"]["fold.fetch"] for b in beams
         if not b["error"] and "fold.fetch" in b["timers"]]
    return sum(t) / len(t) if t else None
