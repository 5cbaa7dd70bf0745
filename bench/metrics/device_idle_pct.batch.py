"""Device idle share in a traced job: 1 - busy / window, averaged over
the chips used; busy is the union of device-op intervals."""


def read(view):
    return view.summary.idle_pct()
