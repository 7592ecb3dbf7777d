"""Output tokens of every engine wave the window holds, over the time
of those waves (the window starts and ends on a wave boundary)."""


def read(run):
    tokens = run.counters.get("tokens")
    if not tokens:
        return None
    return tokens / run.record["seconds"]
