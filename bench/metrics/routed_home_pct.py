"""Share of the requests routed in the window that the router placed on
their namespace's home replica, the one whose trie and prefix cache they
warmed (``FleetStats.affinity_hits / routed``; the rest spilled)."""


def read(run):
    c = run.counters
    if c.get("routed", 0) <= 0:
        return None
    return 100.0 * c["affinity_hits"] / c["routed"]
