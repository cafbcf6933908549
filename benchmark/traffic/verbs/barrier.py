"""Wait for every session still looping (mdtest's MPI_Barrier between
its phases). Not a timed operation: the wait is the window's time."""


async def do(t, s, st, arg, warm):
    await t.barrier.wait()
