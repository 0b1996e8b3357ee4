"""Served path, client to bound event: 99th percentile over every pod due in
the window of (bound event on the client's watch - the instant the pod was
due), on the client's clock, as the open loop's driver works it out beside its
end-to-end numbers (`bind_p99_ms` there; 176 samples lie beyond it in a 40 s
window). It was end to end until PR 26: its runs spread by more than any bound
admits, so the guard on the tail is `bind_within_200ms_share` and this stands
beside it, read and unguarded (PERF.md sections 2 and 7)."""


def read(obs):
    return ((obs.get("client") or {}).get("e2e") or {}).get("bind_p99_ms")
