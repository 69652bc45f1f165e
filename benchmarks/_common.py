"""Shared benchmark plumbing."""

import asyncio
import time


async def wait_until(check, why: str, timeout: float = 30.0, interval: float = 0.01) -> None:
    """Poll `check` (exceptions count as not-yet) until true or timeout."""
    deadline = time.monotonic() + timeout
    while True:
        try:
            if check():
                return
        except Exception:
            pass
        if time.monotonic() > deadline:
            raise TimeoutError(why)
        await asyncio.sleep(interval)


async def wait_synced(providers, why: str = "providers never synced", timeout: float = 60.0) -> None:
    await wait_until(lambda: all(p.synced for p in providers), why, timeout, 0.005)
