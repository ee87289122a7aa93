"""Share of the tokens the window layers HOLD in their pages that no query
can see any more: 1 - ``dispatch/kv_window_live_tokens`` ÷
``dispatch/kv_window_held_tokens`` (host arithmetic on each packed batch's
row lengths and the window, ``engine_v2._kv_window_tokens``; ramp and
window). What a scheduler that freed pages behind the window would free."""

from benchmark.trace import scopes

LAYER = "scheduler"
MOVES = "serve_tokens_per_s"


def read(run):
    live = scopes.counter_value("dispatch/kv_window_live_tokens")
    held = scopes.counter_value("dispatch/kv_window_held_tokens")
    if live is None or not held:
        return None
    return 100.0 * (1.0 - live / held)
