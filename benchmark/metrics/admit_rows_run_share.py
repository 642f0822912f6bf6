"""Token-layer rows the admission programs ran, of the rows a program
that runs every padded token through every layer would have
(ServingEngine.stats `stack_rows_run` over `stack_rows_all`, window
delta). A model whose upper layers keep no cache (models/phi_flash.py:
14 of 32) runs them, and the last cache's query, on the ONE row whose
logits the admission keeps: some 53 % says it did, 100 % that every
row ran every layer. A program without the counters gives nothing.

Moves itl_mean_ms: every admission runs on the one engine thread and
stalls all decoding slots for as long as its program takes.
"""

KIND = "per_layer"
LAYER = "Model step"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "itl_mean_ms"


def read(obs):
    every = obs.counters.get("stack_rows_all", 0)
    if not every:
        return None
    return 100.0 * obs.counters.get("stack_rows_run", 0) / every
