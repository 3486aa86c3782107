"""Mean wait of a request in the engine's queue, from admission to the
dequeue of its batch: counters ``serve.queue_wait_us / serve.scored``
moved in the window, in ms.  None where the program has no such counter."""


def read(run):
    wait_us = run.counters.get("serve.queue_wait_us")
    scored = run.counters.get("serve.scored", 0)
    if wait_us is None or not scored:
        return None
    return wait_us / scored / 1e3
