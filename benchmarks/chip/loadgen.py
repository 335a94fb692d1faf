"""The load generator: one process, no JAX, drives the server over HTTP
with the binary SDK (``repro.client.CoresetClient``).

    python loadgen.py SPEC.json

SPEC names the server's URL, the traffic mix, the configuration, the seed,
the window's seconds and the output path.  The process draws the records
it will insert and their order, prints ``READY``, waits for a line
``GO <t0>`` on stdin (``t0`` on the system-wide monotonic clock, which the
server process shares), runs the window and writes one ``.npz`` of
per-request records: when each register was sent, when it and the build
after it answered (monotonic seconds), whether both succeeded, their
server trace ids, what the build answered and which record it was.
"""
from __future__ import annotations

import json
import pathlib
import sys
import threading
import time

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from repro.client import CoresetClient  # noqa: E402
from yardstick import traffic  # noqa: E402

# answers may come up to this long after the window closes; later ones are
# counted as never answered
GRACE_S = 60.0


def _client(url: str) -> CoresetClient:
    """A client that has made one request already (the first call of a
    process pays for imports and connection set-up, outside the window)."""
    cl = CoresetClient(url, encoding="binary", timeout=GRACE_S + 30.0)
    cl.healthz()
    return cl


def _err(exc: BaseException) -> str:
    return f"{type(exc).__name__}: {exc}"[:300]


def run_closed_builds(spec: dict, mix: dict, config: dict) -> dict:
    seconds, seed = float(spec["seconds"]), int(spec["seed"])
    clients = int(mix["clients"])
    k, eps = int(config["coreset"]["k"]), float(config["coreset"]["eps"])
    rows: list[list] = [[] for _ in range(clients)]
    conns = [_client(spec["url"]) for _ in range(clients)]
    order = traffic.insert_order(config, seed)
    records = [traffic.record_at(mix, config, i) for i in range(len(order))]
    say_ready()
    t0 = wait_go()
    t_end = t0 + seconds

    def loop(c: int) -> None:
        cl = conns[c]
        j = 0
        delay = t0 - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        while time.monotonic() < t_end:
            i = traffic.insert_index(mix, order, c, j)
            name = traffic.insert_name(c, j)
            s = time.monotonic()
            reg = blocks = -1.0
            tid_reg = tid_build = err = ""
            eps_eff = np.nan
            good = False
            try:
                cl.register_signal(name, values=records[i].values)
                reg = time.monotonic()
                tid_reg = cl.last_trace_id or ""
                b = cl.build(name, k, eps)
                tid_build = cl.last_trace_id or ""
                blocks, eps_eff, good = b.blocks, b.eps_eff, True
            except Exception as exc:  # noqa: BLE001 - recorded as failed
                err = _err(exc)
            rows[c].append([c, j, s, reg, time.monotonic(), good, blocks,
                            eps_eff, tid_reg, tid_build, err, i])
            j += 1

    threads = [threading.Thread(target=loop, args=(c,)) for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    flat = [r for rs in rows for r in rs]
    return {"t0": np.float64(t0),
            "client": np.array([r[0] for r in flat], np.int64),
            "j": np.array([r[1] for r in flat], np.int64),
            "sent": np.array([r[2] for r in flat]),
            "registered": np.array([r[3] for r in flat]),
            "done": np.array([r[4] for r in flat]),
            "ok": np.array([r[5] for r in flat], bool),
            "blocks": np.array([r[6] for r in flat], np.int64),
            "eps_eff": np.array([r[7] for r in flat]),
            "trace_register": np.array([r[8] for r in flat], "U32"),
            "trace_build": np.array([r[9] for r in flat], "U32"),
            "error": np.array([r[10] for r in flat], "U300"),
            "record": np.array([r[11] for r in flat], np.int64)}


def say_ready() -> None:
    print("READY", flush=True)


def wait_go() -> float:
    line = sys.stdin.readline().split()
    if len(line) != 2 or line[0] != "GO":
        sys.exit(f"loadgen: expected 'GO <t0>', got {line!r}")
    return float(line[1])


def main() -> int:
    spec = json.loads(pathlib.Path(sys.argv[1]).read_text())
    mix, config = spec["traffic"], spec["config"]
    if mix["loop"] == "closed" and mix["route"] == "register_build":
        out = run_closed_builds(spec, mix, config)
    else:
        sys.exit(f"loadgen: cannot run a {mix['loop']} loop of "
                 f"{mix['route']}")
    np.savez(spec["out"], **out)
    print("DONE", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
