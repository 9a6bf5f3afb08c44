"""Shared test helpers."""

import asyncio


async def wait_until(cond, timeout=5.0, interval=0.02):
    """Poll ``cond`` until true (the reference's test/utils.js wait())."""
    deadline = asyncio.get_event_loop().time() + timeout
    while not cond():
        if asyncio.get_event_loop().time() > deadline:
            raise TimeoutError('condition never became true')
        await asyncio.sleep(interval)


async def mntr_rows(port: int, timeout: float = 5.0) -> dict:
    """One member's ``mntr`` over raw TCP as ``{key: value-string}``."""
    reader, writer = await asyncio.open_connection('127.0.0.1', port)
    try:
        writer.write(b'mntr')
        await writer.drain()
        text = (await asyncio.wait_for(reader.read(), timeout)).decode()
    finally:
        writer.close()
    return dict(line.split('\t', 1) for line in text.strip().splitlines())
