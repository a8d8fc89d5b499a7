"""Model code: the most ROUNDS past the first that any sparse layer's
expert-parallel exchange needed (``monitor.train.moe_exchange_tier``: the
fullest destination of any chip over the rows a round carries, less one; 0
under balance), the larger of the reading before the window and the one
after it (``.end``: the routing moves while the window trains; the cell's
driver takes both).  A layer past 0 pays a second round of pack,
``all_to_all``, grouped matmuls and sum in that step.

It does NOT cover the window itself: no call inside it is observed (a
monitor session there would be timed), so a step BETWEEN the two readings
that took a second round shows in the step times' tail (the ``window:``
line's longest sample beside ``step_ms_p50``) and in
``scripts/kanana2_routing_watch.py``'s readings every ten steps, not here.

A run without the counter (a driver that opens no session, a program
without the exchange) reads nothing."""

NAME = "monitor.train.moe_exchange_tier"


def read(trace, spans, counters, cell):
    seen = [counters[k] for k in (NAME, NAME + ".end") if k in counters]
    if not seen:
        return None
    fullest = [counters.get("monitor.train.moe_exchange_fullest" + end)
               for end in ("", ".end")]
    cell["say"]("ep_tier_max: %s before and after the window; the fullest "
                "destination %s of a capacity of %s rows"
                % (seen, fullest,
                   counters.get("monitor.train.moe_exchange_capacity")))
    return float(max(seen))
