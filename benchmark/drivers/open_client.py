#!/usr/bin/env python3
"""The client side of the open loop: two JAX-free processes of one file.

Started by `drivers/open.py`, once per role, so that decoding the watch never
delays a send (one interpreter lock each):

- `--role sender`: a few keep-alive connections. On
  `phase <tag> <seconds> <rate> <out.json>` it sends one pod per POST at the
  due instants of a Poisson schedule and writes, per pod, the due instant,
  the instant it was sent and the instant the POST was answered.
- `--role watcher`: one pod watch on the apiserver (the program's own
  `HTTPClientset` reflector: the client a controller would use). On
  `dump <out.json>` it writes the order in which pods were added, and for each
  bound pod its node and the instant the bound event arrived.

All instants are `time.perf_counter()`, which on Linux is CLOCK_MONOTONIC and
so one clock for every process of the machine. Commands come on stdin, one per
line; each is answered on stdout (`ready`, `done <what>`).

The schedule: `round(rate * seconds)` exponential gaps from a fixed sample,
scaled to sum to `seconds` and put into another order by the seed, so that
every seed offers the same set of gaps.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

GAP_SAMPLE_SEED = 20260927      # the one fixed sample every seed permutes


def schedule(rate: float, seconds: float, seed: int, phase: int) -> np.ndarray:
    n = max(1, int(round(rate * seconds)))
    gaps = np.random.default_rng(GAP_SAMPLE_SEED).exponential(1.0, n)
    gaps *= seconds / gaps.sum()
    np.random.default_rng([int(seed), 2, phase]).shuffle(gaps)
    return np.cumsum(gaps)


def watcher(args) -> int:
    from kubernetes_tpu.core.apiserver import HTTPClientset
    added, bound_at, node_of = [], {}, {}

    def on_pod(kind, old, new):
        if kind == "add":
            added.append(new.name)
        if new.node_name and new.name not in bound_at and kind != "delete":
            bound_at[new.name] = time.perf_counter()
            node_of[new.name] = new.node_name

    watch = HTTPClientset(args.base)
    watch.on_pod_event(on_pod)
    print("ready", flush=True)
    for line in sys.stdin:
        words = line.split()
        if not words:
            continue
        if words[0] == "quit":
            break
        with open(words[1], "w") as f:
            json.dump({"order": list(added), "bound_at": dict(bound_at),
                       "node_of": dict(node_of)}, f)
        print("done dump", flush=True)
    watch.close()
    return 0


def sender(args) -> int:
    import objects
    from kubernetes_tpu.core.apiserver import KeepAliveClient, pod_to_wire
    proto = objects.make_pod_prototype(json.loads(args.template),
                                       args.bench_dir)
    poster = KeepAliveClient(args.base)
    print("ready", flush=True)
    phase_no = 0
    for line in sys.stdin:
        words = line.split()
        if not words:
            continue
        if words[0] == "quit":
            break
        _, tag, seconds, rate, out_path = words
        seconds, rate = float(seconds), float(rate)
        phase_no += 1
        due = schedule(rate, seconds, args.seed, phase_no)
        n = len(due)
        names = [f"{tag}-{i}" for i in range(n)]
        wires = [pod_to_wire(objects.stamp(proto, name)) for name in names]
        sent = np.full(n, np.nan)
        answered = np.full(n, np.nan)
        errors = []
        nxt = [0]
        lock = threading.Lock()
        t0 = time.perf_counter() + 0.05
        cpu0 = time.process_time()

        def send():
            while True:
                with lock:
                    i = nxt[0]
                    nxt[0] += 1
                if i >= n:
                    return
                wait = t0 + due[i] - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                sent[i] = time.perf_counter()
                try:
                    poster.call("POST", "/api/v1/pods", wires[i])
                except Exception as e:  # noqa: BLE001 - counted, reported
                    errors.append(f"{names[i]}: {type(e).__name__}: {e}"[:200])
                answered[i] = time.perf_counter()

        threads = [threading.Thread(target=send, daemon=True)
                   for _ in range(args.connections)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # this process's CPU seconds per second of schedule: near 1, the
        # generator and not the system under test would set the pace
        cpu_share = (time.process_time() - cpu0) / max(
            time.perf_counter() - t0, 1e-9)
        with open(out_path, "w") as f:
            json.dump({"tag": tag, "rate": rate, "seconds": seconds,
                       "names": names, "t0": t0,
                       "due": [float(x) for x in t0 + due],
                       "sent": [float(x) for x in sent],
                       "answered": [float(x) for x in answered],
                       "post_errors": errors[:20],
                       "n_post_errors": len(errors),
                       "cpu_share": cpu_share}, f)
        print(f"done {tag}", flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--role", choices=("sender", "watcher"), required=True)
    ap.add_argument("--root", required=True)
    ap.add_argument("--base", required=True)
    ap.add_argument("--template", default="{}", help="pod template, JSON")
    ap.add_argument("--bench-dir", default=None,
                    help="where pod features are looked for first")
    ap.add_argument("--connections", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    # These two processes are the benchmark's instruments, and short-lived:
    # a pause of their own cyclic collector (tens of milliseconds once a
    # phase's pods are alive) would be read as the system's latency.
    import gc
    gc.disable()
    sys.path.insert(0, args.root)
    sys.path.insert(0, os.path.join(args.root, "benchmark"))
    return watcher(args) if args.role == "watcher" else sender(args)


if __name__ == "__main__":
    sys.exit(main())
