"""Host-clock load generator: submits each request when it is due and
drives ``ServeEngine.step()`` in a loop, from one thread.

Every output token is stamped with the host time at which the ``step()``
that produced it returned (the engine has synced with the device by
then: it reads the sampled tokens back). Times are seconds from the
start of the window.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

from traffic import Item


@dataclasses.dataclass
class Record:
    rid: int
    prompt_len: int
    max_new: int
    due: float
    submit: float
    admit: Optional[float] = None      # start of the step() that admitted it
    stamps: List[float] = dataclasses.field(default_factory=list)
    done: bool = False
    request: Any = None                # the engine's Request (tokens served)


@dataclasses.dataclass
class Step:
    """One ``step()``: host start and end, and the cache length of each
    slot it decoded (positions valid after the step's write)."""
    start: float
    end: float
    prefills: List[int]                 # real prompt lengths it admitted
    decode_lens: List[int]


@dataclasses.dataclass
class Drive:
    records: List[Record]
    steps: List[Step]
    window_s: float
    closed_at: float                    # when the drain ended


def drive(engine, items: List[Item], *, seconds: float, drain_s: float,
          backlog: bool, make_request: Callable,
          on_open: Optional[Callable] = None,
          on_close: Optional[Callable] = None) -> Drive:
    """Run the window, then follow every submitted request to its last
    token for at most ``drain_s`` more seconds.

    Open loop: ``items`` carry due times; each is submitted at the first
    loop turn at or after it. Backlog: the engine's queue is kept at
    ``engine.slots`` waiting requests until the window closes.
    ``on_open`` runs just before the window opens, ``on_close`` at the
    first loop turn after it closed."""
    recs: Dict[int, Record] = {}
    live: Dict[int, tuple] = {}         # rid -> (Request, Record)
    steps: List[Step] = []
    nxt, open_ = 0, True
    if on_open is not None:
        on_open()
    t0 = time.perf_counter()

    def submit(item: Item, due: float, now: float):
        req = make_request(item)
        rec = Record(item.rid, len(item.prompt), item.max_new, due, now,
                     request=req)
        recs[item.rid] = rec
        live[item.rid] = (req, rec)
        engine.submit(req)

    while True:
        now = time.perf_counter() - t0
        if now < seconds:
            if backlog:
                while len(engine.queue) < engine.slots and nxt < len(items):
                    submit(items[nxt], now, now)
                    nxt += 1
            else:
                while nxt < len(items) and items[nxt].due <= now:
                    submit(items[nxt], items[nxt].due, now)
                    nxt += 1
        else:
            if open_ and on_close is not None:
                on_close()
            open_ = False
            if not live or now >= seconds + drain_s:
                break
        if not engine.queue and not any(engine.active):
            if backlog or nxt >= len(items):
                if now >= seconds:
                    break
                time.sleep(min(1e-3, seconds - now))
            else:
                time.sleep(max(0.0, min(items[nxt].due, seconds) - now))
            continue
        start = time.perf_counter() - t0
        engine.step()
        end = time.perf_counter() - t0
        step = Step(start, end, [], [])
        for rid, (req, rec) in list(live.items()):
            new = len(req.out_tokens) - len(rec.stamps)
            if not new:
                continue
            if rec.admit is None:           # its prefill ran in this step
                rec.admit = start
                step.prefills.append(rec.prompt_len)
            # every slot with a request decodes one token per step: the
            # cache then holds the prompt and all tokens but the newest
            step.decode_lens.append(rec.prompt_len + len(req.out_tokens) - 1)
            rec.stamps.extend([end] * new)
            if req.done:
                rec.done = True
                del live[rid]
        steps.append(step)
    return Drive(sorted(recs.values(), key=lambda r: r.rid), steps, seconds,
                 time.perf_counter() - t0)
