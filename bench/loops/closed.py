"""Closed loop: ``clients`` callers, each sending its next request as
soon as the previous one ends. Client ``c`` sends requests ``c``,
``c + clients``, ``c + 2 clients``, ... while the window is open; each
request is timed from its send (a closed loop has no schedule)."""
from __future__ import annotations

import threading
import time


def drive(send, params: dict, seed: int, t_start: float, t_end: float,
          deadline: float) -> None:
    del seed                      # the request order is the pool's
    n = int(params["clients"])
    errors: list[BaseException] = []

    def client(c: int) -> None:
        try:
            time.sleep(max(0.0, t_start - time.monotonic()))
            k = c
            while time.monotonic() < t_end:
                send(k, time.monotonic())
                k += n
        except BaseException as e:          # noqa: BLE001 — re-raised
            errors.append(e)

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=max(0.0, deadline - time.monotonic()) + 30.0)
    if errors:
        raise errors[0]
