"""The benchmark's event source: one generator for every traffic mix.

An event is one training sequence, named by its offset in the stream;
the trainer's batcher turns offsets into tokens.  The source offers the
interface the trainer consumes (``produce_until``, ``consume``, ``lag``,
``cursor``/``restore``, ``rate_at``) and is saturating: a full batch is
always waiting, so the trainer never underruns (an underrun would advance
its virtual job clock by time that did not pass).
"""
from __future__ import annotations


class EventSource:
    """Saturating event source with the trainer's stream interface.
    Offsets are whole numbers; ``produced`` counts the events that have
    arrived."""

    def __init__(self, batch: int):
        self.batch = batch
        self.produced = 0
        self.consumed = 0

    def rate_at(self, t: float) -> float:
        return 0.0

    def produce_until(self, t: float) -> None:
        self.produced = max(self.produced, self.consumed + self.batch)

    @property
    def lag(self) -> int:
        return max(0, self.produced - self.consumed)

    def consume(self, n: int) -> int:
        take = min(n, self.lag)
        self.consumed += take
        return take

    def cursor(self) -> dict:
        return {"consumed": self.consumed}

    def restore(self, cursor: dict) -> None:
        self.consumed = int(cursor["consumed"])
