"""One replica of a configuration served as N one-chip replicas (its
``replicas`` key), in a process of its own.

Run by the harness (`cell.Tier`) as ``python replica.py <plan.json>``, with
the chip it holds pinned in its environment (`cell.pin`).  It builds its
engine exactly as the harness's replica 0 does (`cell.Served`, from the
same seed, so the same corpus, generated on its own chip) and serves it
over HTTP.  It talks to its parent in lines on stdin/stdout:

  -> ``device <json>``           JAX found its chip: platform, kind, count
                                 (``nochip <why>``, exit 3, where it did not)
  <- ``build``                   every process of the tier found its chip
  -> ``url <base url>``          loaded, built, warmed and serving
  <- ``trace <t_a> <seconds>``   optional: trace its chip over that span of
                                 the shared monotonic clock
  <- ``stop``
  -> ``stopped <json>``          server, driver and engine released: its
                                 chip's memory peak, the traced span's
                                 busy seconds (0 where it traced nothing),
                                 and when it found its chip and served
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from harness import cell  # noqa: E402
from harness import trace as tr  # noqa: E402


def say(line: str) -> None:
    sys.stdout.write(line + "\n")
    sys.stdout.flush()


def serve(plan, dev, rundir: str) -> dict:
    """Build, serve until told to stop, and release; returns what the
    ``stopped`` line reports."""
    from harness.corpus import Corpus
    from harness.faults import PLANTED

    cfg = plan["config"]
    corpus = Corpus(plan["seed"], int(cfg["n_docs"]), int(cfg["dim"]),
                    cfg["corpus"])
    fault = plan["fault"]
    log_dir = os.path.join(rundir, "trace")
    traced_ns = 0.0
    with cell.Served(cfg, corpus, trace=plan["trace"],
                     tamper=PLANTED[fault] if fault else None) as served:
        t_url = time.monotonic() - T_START
        say(f"url {served.handle.url}")
        for line in sys.stdin:
            word, _, rest = line.strip().partition(" ")
            if word == "trace":
                t_a, length = (float(x) for x in rest.split())
                cell.sleep_until(t_a)
                traced_ns = cell.profile(log_dir, length)
            elif word == "stop":
                break
    busy_s = 0.0
    if traced_ns:
        busy_s = tr.summarize(tr.read_xplane(tr.find_xplane(log_dir)),
                              traced_ns).busy_s
    return {"memory_peak": int((dev.memory_stats() or {}).get(
        "peak_bytes_in_use", 0)), "busy_s": busy_s, "t_url": round(t_url, 2)}


def main() -> int:
    with open(sys.argv[1]) as f:
        plan = json.load(f)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        devs = cell.devices(plan["require_tpu"], 1)
    except cell.NoChip as e:
        say(f"nochip {e}")
        return 3
    dev = devs[0]
    cell.compile_cache()
    t_device = time.monotonic() - T_START
    say("device " + json.dumps({"platform": dev.platform,
                                "kind": dev.device_kind,
                                "count": len(devs)}))
    if sys.stdin.readline().strip() != "build":
        return 1
    rundir = tempfile.mkdtemp(prefix="bench-replica-")
    try:
        say("stopped " + json.dumps(dict(serve(plan, dev, rundir),
                                         t_device=round(t_device, 2))))
    finally:
        shutil.rmtree(rundir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
