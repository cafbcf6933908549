"""Hand the batch of files the session has made to the others (mdtest's
ranks name each other's files by rank) and start a new one."""


async def do(t, s, st, arg, warm):
    t.shared[s] = st["made"]
    st["made"] = []
