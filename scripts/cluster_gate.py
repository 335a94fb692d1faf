#!/usr/bin/env python
"""ci_smoke ``cluster`` gate: the distributed serving plane, end to end.

Boots the cluster the way an operator would — 1 coordinator + 3 workers as
SEPARATE PROCESSES via ``python -m repro.launch.serve_coresets --role ...``
— drives the coordinator's full v1 API through the typed SDK, and asserts
the invariants the plane is built on:

  * a coreset gathered from 3 remote band builds is **bitwise
    fingerprint-equal** to the single-host thread-pool build, and every
    loss answer is within 1e-9 of the single-host engine;
  * killing a worker degrades gracefully: requests keep answering 200
    (never a 5xx storm), the composed coreset keeps the SAME fingerprint
    (the coordinator rebuilds the orphaned band locally with the identical
    tolerance), and only ``cluster.degraded_builds`` moves;
  * restarting an EMPTY worker on the same port rejoins it: the
    content-addressed no_band/stale_band heal re-assigns the slab, no new
    degraded builds happen, and ``cluster.worker_rejoins`` ticks.

One process per device: the single-host reference (fingerprints and
losses of every signal the gate registers) is computed up front by a child
process that exits before any worker starts, and the gate process itself
never loads JAX — it only drives servers through the SDK.  On a chip host
the four-chip form of this check is ``python chip_smoke.py --chips 4``.

Run:  python scripts/cluster_gate.py [--reprobe 0.5]
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import re
import subprocess
import sys
import threading
import time
import zlib

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

from repro.client import CoresetClient  # noqa: E402
from repro.core.segmentation import random_tree_segmentation  # noqa: E402
from repro.data.signals import piecewise_signal  # noqa: E402

N, M, K, EPS = 96, 64, 6, 0.3
# (signal name, generator seed, loss queries) for each phase of the gate
SIGNALS = (("sig", 7, 4), ("sig-degraded", 8, 6), ("sig-rejoin", 9, 4))
_URL_RE = re.compile(r"listening on (http://[\d.]+:\d+)")


class _Proc:
    """A serve_coresets subprocess plus a drain thread over its stdout."""

    def __init__(self, role_args: list[str]):
        env = dict(os.environ)
        env["PYTHONPATH"] = "src" + (os.pathsep + env["PYTHONPATH"]
                                     if env.get("PYTHONPATH") else "")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "repro.launch.serve_coresets", *role_args],
            cwd=ROOT, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True, bufsize=1)
        self.lines: list[str] = []
        self.url: str | None = None
        threading.Thread(target=self._drain, daemon=True).start()

    def _drain(self) -> None:
        for line in self.proc.stdout:
            self.lines.append(line)
            m = _URL_RE.search(line)
            if m and self.url is None:
                self.url = m.group(1)

    def wait_url(self, timeout: float = 60.0) -> str:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < timeout:
            if self.url:
                return self.url
            if self.proc.poll() is not None:
                break
            time.sleep(0.02)
        raise RuntimeError("subprocess never reported its URL:\n"
                           + "".join(self.lines))

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=10)


def _port(url: str) -> int:
    return int(url.rsplit(":", 1)[1])


def _queries(name: str, count: int) -> list:
    # seeded from the name's CRC (str hashes differ between processes)
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    return [random_tree_segmentation(N, M, K, rng) for _ in range(count)]


def _reference() -> None:
    """Child process: the single-host build of every gate signal, printed
    as one JSON line {name: {"fingerprint", "losses"}}."""
    from repro.service import CoresetEngine
    single = CoresetEngine(num_bands=3, workers=4)
    out = {}
    try:
        for name, seed, count in SIGNALS:
            single.register_signal(name, piecewise_signal(N, M, K, seed=seed))
            cs, _, _ = single.get_coreset(name, K, EPS)
            out[name] = {"fingerprint": cs.fingerprint(), "losses": [
                single.tree_loss(name, q.rects, q.labels, eps=EPS)["loss"]
                for q in _queries(name, count)]}
    finally:
        single.close()
    print(json.dumps(out))


def _single_host_reference() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + (os.pathsep + env["PYTHONPATH"]
                                 if env.get("PYTHONPATH") else "")
    proc = subprocess.run([sys.executable, __file__, "--reference"],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(f"single-host reference failed:\n{proc.stderr}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _parity(client: CoresetClient, reference: dict, signal: tuple,
            errors: list[str]) -> None:
    """Register + build + query one gate signal on the cluster; any
    fingerprint or loss divergence from the single-host reference is
    appended to ``errors``."""
    name, seed, count = signal
    ref = reference[name]
    client.register_signal(name, values=piecewise_signal(N, M, K, seed=seed))
    rb = client.build(name, K, EPS)
    if rb.fingerprint != ref["fingerprint"]:
        errors.append(f"{name}: cluster fingerprint {rb.fingerprint[:12]} != "
                      f"single-host {ref['fingerprint'][:12]}")
    for q, ls in zip(_queries(name, count), ref["losses"]):
        rc = client.query_loss(name, q.rects, q.labels, eps=EPS)
        if abs(rc.loss - ls) > 1e-9:
            errors.append(f"{name}: loss off single-host by "
                          f"{abs(rc.loss - ls):.2e} > 1e-9")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reprobe", type=float, default=0.5,
                    help="coordinator down-worker cooldown seconds")
    ap.add_argument("--rpc-timeout", type=float, default=15.0)
    ap.add_argument("--reference", action="store_true",
                    help=argparse.SUPPRESS)   # the reference child's role
    args = ap.parse_args()
    if args.reference:
        _reference()
        return 0

    procs: list[_Proc] = []
    errors: list[str] = []
    try:
        reference = _single_host_reference()
        workers = [_Proc(["--role", "worker", "--host", "127.0.0.1",
                          "--port", "0", "--worker-id", f"gate-w{i}"])
                   for i in range(3)]
        procs += workers
        peer_urls = [w.wait_url() for w in workers]
        coord = _Proc(["--role", "coordinator", "--host", "127.0.0.1",
                       "--port", "0", "--peers", ",".join(peer_urls),
                       "--reprobe-s", str(args.reprobe),
                       "--rpc-timeout", str(args.rpc_timeout)])
        procs.append(coord)
        base = coord.wait_url()
        client = CoresetClient(base, retries=0)
        print(f"[cluster_gate] coordinator {base}, workers "
              f"{[_port(u) for u in peer_urls]}")

        # ---- healthy plane: bitwise fingerprint + 1e-9 loss parity
        _parity(client, reference, SIGNALS[0], errors)
        st = client.stats()["cluster"]
        if st["degraded_builds"] != 0:
            errors.append(f"healthy build degraded {st['degraded_builds']}x")
        if [p["up"] for p in st["peers"]] != [True] * 3:
            errors.append(f"healthy plane reports down peers: {st['peers']}")
        print(f"[cluster_gate] healthy: fingerprint parity OK, "
              f"gathers={st['gathers']} degraded=0")

        # ---- kill a worker: degrade, never 5xx, identical bytes
        victim = workers[1]
        victim_port = _port(peer_urls[1])
        victim.kill()
        _parity(client, reference, SIGNALS[1], errors)
        st = client.stats()["cluster"]
        degraded = st["degraded_builds"]
        if degraded < 1:
            errors.append("worker killed but no degraded build recorded")
        if all(p["up"] for p in st["peers"]):
            errors.append("killed worker still reported up")
        print(f"[cluster_gate] degraded: parity survives worker kill "
              f"(degraded_builds={degraded}, all requests 200)")

        # ---- rejoin: empty worker on the SAME port heals via re-assign
        fresh = _Proc(["--role", "worker", "--host", "127.0.0.1",
                       "--port", str(victim_port), "--worker-id", "gate-w1b"])
        procs.append(fresh)
        fresh.wait_url()
        time.sleep(args.reprobe + 0.2)   # let the cooldown lapse
        _parity(client, reference, SIGNALS[2], errors)
        st = client.stats()["cluster"]
        if st["degraded_builds"] != degraded:
            errors.append(f"rejoin still degraded: {st['degraded_builds']} "
                          f"builds vs {degraded} before restart")
        if st["worker_rejoins"] < 1:
            errors.append("restarted worker never marked rejoined")
        if not all(p["up"] for p in st["peers"]):
            errors.append(f"rejoined plane reports down peers: {st['peers']}")
        print(f"[cluster_gate] rejoin: worker back on :{victim_port}, "
              f"rejoins={st['worker_rejoins']}, degraded stayed {degraded}")
    except Exception as exc:  # noqa: BLE001
        errors.append(f"{type(exc).__name__}: {exc}")
    finally:
        for p in procs:
            p.kill()

    if "jax" in sys.modules:
        errors.append("the gate process loaded JAX (it must only drive "
                      "servers)")
    for e in errors:
        print(f"[cluster_gate] FAIL: {e}")
    print(f"[cluster_gate] {'PASS' if not errors else 'FAIL'}")
    return 0 if not errors else 1


if __name__ == "__main__":
    sys.exit(main())
