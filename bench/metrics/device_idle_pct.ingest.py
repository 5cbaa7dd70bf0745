"""Device idle share over a traced stretch of ingests and snapshots:
1 - busy / window, averaged over the chips used."""


def read(view):
    return view.summary.idle_pct()
