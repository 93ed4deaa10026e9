"""Mean per beam of the ``device.fetches`` counter (each beam's
telemetry.json): blocking device-to-host transfers on the beam's path."""

import json
import os


def read(trace, beams, ctx):
    n = []
    for b in beams:
        if b["error"]:
            continue
        try:
            with open(os.path.join(b["outdir"], "telemetry.json")) as f:
                counters = json.load(f).get("counters", {})
        except (OSError, ValueError):
            continue
        if "device.fetches" in counters:
            n.append(counters["device.fetches"])
    return sum(n) / len(n) if n else None
