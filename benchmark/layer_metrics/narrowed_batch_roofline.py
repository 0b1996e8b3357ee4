"""Kernels: the least bytes the traced waves' NARROWED batches must move at
the chip's peak HBM bandwidth (peaks.json), over the measured kernel time of
the scheduling programs. A narrowed batch places its pods on the nodes a
PreFilterResult names and on no other, so the least it must read and write
is THOSE rows once (`kernelcost.least_bytes_per_batch` with `nodes` the
stat `narrowed_rows` that the program's `sched.device.dispatch` span says,
`pods` its stat `batch`, no zones), not the padded rows the plan holds
(`plan_rows`) and not the cluster's: the count is the same whether the
program plans over the named rows or masks every other row of the cluster,
so it cannot pass 100 %, and it reads near 0 for a kernel that sweeps the
whole cluster to place on one node. The kernel time is every scheduling
program's in the traced waves (`tracereduce.kernel_time`), so a wave that
also holds batches that are not narrowed reads lower, never higher. A
program whose dispatch spans carry no `narrowed_rows` (the parent of the PR
that added it; a plan that is not narrowed), a run without a trace and a
rehearsal (no chip) read nothing."""

import kernelcost
import progspans
import spanstats
import tracereduce


def share(kernel_s, rows, pods, device_kind):
    """Percent: the least time of batches of `pods[i]` pods over `rows[i]`
    narrowed rows at peak HBM bandwidth, over `kernel_s`."""
    least = sum(kernelcost.least_bytes_per_batch(int(r), float(p), 0)
                for r, p in zip(rows, pods))
    least_s = least / kernelcost.peaks(device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s


def read(obs):
    got = tracereduce.kernel_time(obs)
    if got is None or obs.get("device", {}).get("rehearsal"):
        return None
    name = progspans.PREFIX + "device.dispatch"
    rows = spanstats.this_runs(obs, name, "narrowed_rows")
    pods = spanstats.this_runs(obs, name, "batch") if rows else None
    if not rows or not pods:
        return None
    waves = int(obs["traced"]["waves"])
    pairs = [(r, p) for r, p in zip(
        spanstats.in_traced_waves(rows[0], rows[1], waves),
        spanstats.in_traced_waves(pods[0], pods[1], waves))
        if r is not None and p is not None]
    if not pairs:
        return None
    return share(got[0], [r for r, _ in pairs], [p for _, p in pairs],
                 obs["device"]["kind"])
