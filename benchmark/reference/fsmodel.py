"""An in-memory model of the file system the traffic drives: names,
lengths and contents, under the semantics the configurations state
(create makes an empty file, a write from offset 0 sets contents and
length, sequential writes extend it, unlink removes the name). Contents are kept
as (offset, length) into the run's seeded byte pool, so the model holds
no copy of the data. Imports nothing of the program under test.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


def make_pool(seed: int, nbytes: int) -> np.ndarray:
    """The run's bytes, a pure function of the seed."""
    words = np.random.Generator(np.random.PCG64([seed, 0x706F6F6C]))
    return words.bit_generator.random_raw(-(-nbytes // 8)).view(np.uint8)[:nbytes]


@dataclass
class File:
    name: str
    inode: int
    dir: int = 0            # index of its directory in the run's list
    pool_off: int = 0
    length: int = 0


class Model:
    def __init__(self, pool: np.ndarray):
        self.pool = pool
        self.files: dict[str, File] = {}

    def create(self, name: str, inode: int, dir: int = 0) -> File:
        if name in self.files:
            raise KeyError(f"{name} exists")
        f = self.files[name] = File(name, inode, dir)
        return f

    def write(self, name: str, pool_off: int, length: int) -> None:
        f = self.files[name]
        f.pool_off, f.length = pool_off, length

    def unlink(self, name: str) -> None:
        del self.files[name]

    def live(self) -> list[File]:
        return list(self.files.values())

    def bytes_of(self, f: File, offset: int = 0,
                 size: int | None = None) -> np.ndarray:
        end = f.length if size is None else min(offset + size, f.length)
        return self.pool[f.pool_off + offset:f.pool_off + max(end, offset)]
