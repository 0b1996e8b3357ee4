"""Feature build and mirror: full plan rebuilds a wave over the window (the
program's counter `plan_rebuilds_full`, the sum of
`scheduler_plan_rebuild_total{kind="full"}`, over the window's waves). A
`.waves` cell pays one for its restore; a churn wave one for every session a
node event or another template's plan ends. Nothing where the program has no
such counter (the host scheduler)."""


def read(obs):
    full = (obs.get("counters") or {}).get("plan_rebuilds_full")
    waves = (obs.get("window") or {}).get("waves")
    if full is None or not waves:
        return None
    return full / waves
