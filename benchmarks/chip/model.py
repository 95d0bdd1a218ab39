"""The coding point of a configuration file, as plain numbers.

Reads the ``coding`` entry of ``configs/<name>.json`` and nothing of the
program, so the reference and the work counts stand apart from the code
under test.  The model's own sizes are its architecture module's
(``archs/<arch>.py``).
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Coding:
    k: int
    s: int
    e: int

    @property
    def workers(self) -> int:
        """N + 1 coded streams per group of K queries."""
        return self.k + self.s if self.e == 0 else \
            2 * (self.k + self.e) + self.s

    @property
    def quorum(self) -> int:
        """Workers a round waits for: K, or K + 2E for the locator."""
        return self.k if self.e == 0 else min(self.k + 2 * self.e,
                                              self.workers)


def coding(config: dict) -> Coding:
    c = config["coding"]
    return Coding(k=int(c["k"]), s=int(c["s"]), e=int(c["e"]))
