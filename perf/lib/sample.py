"""A reservoir of whole call outputs, drawn from the seed: which calls
of the window get judged once it has closed, when not all can be kept."""


class Reservoir:
    """Keeps `size` of the items offered, each with equal chance, and
    always the last one offered."""

    def __init__(self, size, rng):
        self.size, self.rng = size, rng
        self.reset()

    def reset(self):
        self.seen, self.kept, self.last = 0, [], None

    def offer(self, item):
        k = self.seen
        self.seen += 1
        if len(self.kept) < self.size:
            self.kept.append((k, item))
        else:
            j = self.rng.randint(0, k + 1)
            if j < self.size:
                self.kept[j] = (k, item)
        self.last = (k, item)

    def drain(self):
        """{call index: item}, and the reservoir left empty."""
        out = dict(self.kept + ([self.last] if self.last else []))
        self.reset()
        return out
