"""Mean per beam of the ``search.setup`` and ``search.dispatch`` spans
(telemetry.json timers): the host preparing and enqueuing the search
programs."""

SPANS = ("search.setup", "search.dispatch")


def read(trace, beams, ctx):
    t = [sum(b["timers"].get(s, 0.0) for s in SPANS) for b in beams
         if not b["error"] and any(s in b["timers"] for s in SPANS)]
    return sum(t) / len(t) if t else None
