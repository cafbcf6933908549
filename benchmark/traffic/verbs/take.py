"""Take as the current batch what the session ``shift`` places on has
published: mdtest's ``-N`` stride, so that a rank stats, reads and
removes files another rank made and no client cache answers."""


async def do(t, s, st, arg, warm):
    other = (s + int(arg.get("shift", 0))) % len(t.clients)
    st["batch"] = list(t.shared.get(other, []))
