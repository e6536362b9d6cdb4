"""Small asyncio helpers.

`gather_all_cancel_on_error` is what the two fan-out sites (ranged
back-to-source piece fetches, checkpoint multi-file fetch) rely on: run
everything, and on the first failure cancel the stragglers before re-raising
(so multi-GB sibling downloads don't keep running detached after the caller
has already failed). It is asyncio.TaskGroup's cancellation behaviour with
one difference the callers depend on: the first exception propagates BARE,
not wrapped in an ExceptionGroup, so `except IOError` / `except TimeoutError`
at the call sites keep matching.
"""

from __future__ import annotations

import asyncio
from typing import Awaitable, Iterable

__all__ = ["gather_all_cancel_on_error"]


async def gather_all_cancel_on_error(coros: Iterable[Awaitable]) -> None:
    """Await all coroutines; first failure cancels the rest and re-raises.

    Unlike bare asyncio.gather (which returns control on the first error but
    leaves the remaining tasks running detached), every task is finished or
    cancelled by the time this returns, as with asyncio.TaskGroup. The
    first exception (in completion order) propagates; later ones are eaten,
    as with TaskGroup's primary-error behavior for non-ExceptionGroup users.
    """
    tasks = [asyncio.ensure_future(c) for c in coros]
    if not tasks:
        return
    try:
        await asyncio.gather(*tasks)
    except BaseException:
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        raise
