#!/usr/bin/env python3
"""The sender of the closed loop: one JAX-free process.

Started by `drivers/closed.py` beside the watcher role of `open_client.py`
(the bound events are read there, so decoding the watch never delays a send:
one interpreter lock each). Commands come on stdin, one per line; each is
answered on stdout:

- `prepare <tag> <count>`: stamps `count` pods named `<tag>-<i>` from the
  template and groups their wire forms, `--pods-per-post` a request. This is
  the client's own work and lies outside every wave's clock, as the stamping
  of `waves.py` does. Answer: `ready <tag>`.
- `send <tag> <out.json>`: sends the prepared wave, every POST at once over
  `--connections` keep-alive connections (the program's own
  `KeepAliveClient`: the client a controller would use), and writes the pod
  names in request order, the instant before the first POST and, per POST,
  the instants it was sent and answered. Answer: `done <tag>`.
- `quit`.

All instants are `time.perf_counter()`, which on Linux is CLOCK_MONOTONIC and
so one clock for every process of the machine (the watcher's bound instants
are on it too).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", required=True)
    ap.add_argument("--base", required=True)
    ap.add_argument("--template", required=True, help="pod template, JSON")
    ap.add_argument("--bench-dir", default=None,
                    help="where pod features are looked for first")
    ap.add_argument("--connections", type=int, default=4)
    ap.add_argument("--pods-per-post", type=int, default=500)
    args = ap.parse_args(argv)
    # An instrument of the benchmark, and short-lived: a pause of its own
    # cyclic collector would be read as the system's time.
    import gc
    gc.disable()
    sys.path.insert(0, args.root)
    sys.path.insert(0, os.path.join(args.root, "benchmark"))
    import objects
    from kubernetes_tpu.core.apiserver import KeepAliveClient, pod_to_wire
    proto = objects.make_pod_prototype(json.loads(args.template),
                                       args.bench_dir)
    poster = KeepAliveClient(args.base, timeout=120.0)
    prepared = {}
    print("ready", flush=True)
    for line in sys.stdin:
        words = line.split()
        if not words:
            continue
        if words[0] == "quit":
            break
        if words[0] == "prepare":
            _, tag, count = words
            names = [f"{tag}-{i}" for i in range(int(count))]
            wires = [pod_to_wire(objects.stamp(proto, n)) for n in names]
            prepared[tag] = (names, [wires[i:i + args.pods_per_post]
                                     for i in range(0, len(wires),
                                                    args.pods_per_post)])
            print(f"ready {tag}", flush=True)
            continue
        _, tag, out_path = words
        names, posts = prepared.pop(tag)
        sent = [float("nan")] * len(posts)
        answered = [float("nan")] * len(posts)
        errors = []
        nxt = [0]
        lock = threading.Lock()

        def send():
            while True:
                with lock:
                    i = nxt[0]
                    nxt[0] += 1
                if i >= len(posts):
                    return
                sent[i] = time.perf_counter()
                try:
                    poster.call("POST", "/api/v1/pods", posts[i])
                except Exception as e:  # noqa: BLE001 - counted, reported
                    errors.append(f"{tag} POST {i}: {type(e).__name__}: "
                                  f"{e}"[:200])
                answered[i] = time.perf_counter()

        threads = [threading.Thread(target=send, daemon=True)
                   for _ in range(args.connections)]
        t_first = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        with open(out_path, "w") as f:
            json.dump({"tag": tag, "names": names, "t_first_post": t_first,
                       "sent": sent, "answered": answered,
                       "post_errors": errors[:20],
                       "n_post_errors": len(errors)}, f)
        print(f"done {tag}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
