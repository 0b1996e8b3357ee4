"""Feature build and mirror: of the mirror rows that the builds of the traced
waves brought in line with their nodes, the share that took the column pass,
in %. A plan's acquisition syncs the mirror's rows to the snapshot: a row
whose node is the one it encoded and whose pods alone moved has its three
dynamic columns written, many rows in one array pass; every other row is
encoded whole. Each `sched.plan.build` span closes with the stats
`rows_encoded` and `rows_by_column` (the same two numbers count
`scheduler_mirror_rows_total{how}`). A wave cell whose restore deletes a pod
from every node the last wave landed on reads near 100; a cluster whose nodes
change between waves reads lower. A program whose build spans carry no such
stats (the parent of the PR that added them), a run without a trace, and
traced waves whose builds brought no row in line (resumed sessions) read
nothing."""

import progspans
import spanstats


def share(bench, encoded, by_column, waves):
    whole = [float(n) for n in spanstats.in_traced_waves(bench, encoded, waves)
             if n is not None]
    columns = [float(n) for n in
               spanstats.in_traced_waves(bench, by_column, waves)
               if n is not None]
    rows = sum(whole) + sum(columns)
    if not columns or not rows:
        return None
    return 100.0 * sum(columns) / rows


def read(obs):
    name = progspans.PREFIX + "plan.build"
    by_column = spanstats.this_runs(obs, name, "rows_by_column")
    encoded = (spanstats.this_runs(obs, name, "rows_encoded")
               if by_column else None)
    if not by_column or not encoded:
        return None
    return share(by_column[0], encoded[1], by_column[1],
                 int(obs["traced"]["waves"]))
