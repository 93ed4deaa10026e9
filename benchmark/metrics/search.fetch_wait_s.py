"""Mean per beam of the ``search.fetch`` span (telemetry.json timers):
the host blocked on each wave's packed search results, and on any
dedispersion still running before them."""


def read(trace, beams, ctx):
    t = [b["timers"]["search.fetch"] for b in beams
         if not b["error"] and "search.fetch" in b["timers"]]
    return sum(t) / len(t) if t else None
