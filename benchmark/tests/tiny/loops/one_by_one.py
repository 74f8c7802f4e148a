"""A loop added by a file alone: the range-sync pool, offered one block
at a time (each batch one block), so that every verdict is awaited
before the next block goes in."""

from benchmark.loops import range_sync


class Loop(range_sync.Loop):
    def __init__(self, cfg, traffic, rng, sks):
        super().__init__(cfg, traffic, rng, sks)
        self.batches = [[b] for batch in self.batches for b in batch]
