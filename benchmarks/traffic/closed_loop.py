"""Closed loop: a fixed number of clients, each sending its next request
when its last one is done. Callers that wait for a reply."""

from __future__ import annotations

from benchmarks.traffic.lengths import Lengths, prompt_ids


class Source:
    def __init__(self, params: dict, seed: int, vocab: int):
        self.lengths = Lengths(params, seed)
        self.seed, self.vocab = seed, vocab
        self.free = params["clients"]      # clients with nothing outstanding
        self.free_since: list[float] = [0.0] * self.free

    def pop_due(self, t: float, room: int) -> list[dict]:
        """The requests due by `t`, as far as the server's queue has
        room: at the start every client is free at once, and a caller
        that finds the queue full waits and sends when it drains."""
        out = []
        while self.free and len(out) < room:
            i, pl, ol = self.lengths.next()
            self.free -= 1
            out.append({"due": self.free_since.pop(0),
                        "prompt": prompt_ids(self.seed, i, pl, self.vocab),
                        "max_new": ol})
        return out

    def done(self, t: float) -> None:
        self.free += 1
        self.free_since.append(t)

    def next_due(self, t: float) -> float | None:
        return t if self.free else None

    def window_may_open(self, t: float, active: int, slots: int) -> bool:
        """Once every client has sent and every slot has been filled."""
        return self.free == 0 and active == slots
