"""Mean per beam of the ``writing`` and ``write.overview`` spans
(telemetry.json timers): candidates.peasoup and overview.xml. Read only
where the beam carries ``write.overview``, so that it is never
candidates.peasoup alone."""


def read(trace, beams, ctx):
    t = [b["timers"].get("writing", 0.0) + b["timers"]["write.overview"]
         for b in beams
         if not b["error"] and "write.overview" in b["timers"]]
    return sum(t) / len(t) if t else None
