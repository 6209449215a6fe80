"""Device results the program fetched to the host per op in the window
(``host_fetches``, counted where a device array becomes a host array)."""


def read(run):
    fetches = run.counters.get("host_fetches")
    return None if fetches is None else fetches / len(run.op_seconds)
