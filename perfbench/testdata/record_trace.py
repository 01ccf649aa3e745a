"""Record the small device trace that ``test_pb_trace.py`` reads.

    python3 perfbench/testdata/record_trace.py

Runs one jitted program three times, with 50 ms of host sleep before each
run, inside a :class:`pb_trace.Capture`, and copies the ``.xplane.pb`` to
``perfbench/testdata/three_runs.xplane.pb`` with the clock mark and the
runs' ``perf_counter_ns`` intervals in ``three_runs.json``.  Run it on the
chip: the test needs a device plane.
"""

import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import pb_trace  # noqa: E402

SLEEP_S = 0.05


@jax.jit
def three_runs_program(x):
    for _ in range(8):
        x = jnp.tanh(x @ x) * 0.5
    return x


def main() -> int:
    x = jnp.ones((1024, 1024), jnp.float32)
    three_runs_program(x).block_until_ready()          # compile first
    runs = []
    with tempfile.TemporaryDirectory() as tmp:
        with pb_trace.Capture(tmp) as cap:
            for _ in range(3):
                time.sleep(SLEEP_S)
                t0 = time.perf_counter_ns()
                three_runs_program(x).block_until_ready()
                runs.append([t0, time.perf_counter_ns()])
        src = pb_trace.newest_xplane(tmp)
        shutil.copy(src, os.path.join(HERE, "three_runs.xplane.pb"))
    meta = {"mark_pc_ns": cap.mark_pc_ns, "runs_pc_ns": runs,
            "sleep_s": SLEEP_S, "program": "three_runs_program",
            "device_kind": jax.devices()[0].device_kind}
    with open(os.path.join(HERE, "three_runs.json"), "w") as f:
        json.dump(meta, f, indent=1)
    events = pb_trace.load_events(os.path.join(HERE, "three_runs.xplane.pb"))
    lines = sorted({(e.plane, e.line) for e in events})
    for plane, line in lines:
        n = sum(1 for e in events if e.plane == plane and e.line == line)
        print(f"{plane} | {line} | {n}")
    print(os.path.getsize(os.path.join(HERE, "three_runs.xplane.pb")))
    return 0


if __name__ == "__main__":
    sys.exit(main())
