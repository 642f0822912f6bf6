"""How far the routing leans towards or away from this chip: of the
(token, chosen expert) pairs the routers made for real tokens in the
window, admissions and decode steps alike, the share that fell on
experts HELD here (window delta of ServingEngine.stats `moe_pairs_held`
over `moe_pairs_routed`), as its distance in percentage points from the
EVEN share, the held experts over the router's width (the configuration
file's `num_experts` over `expert_share.router_width`: 16 of 128, 12.5).
Over the even share this chip's experts draw more than their part of the
work and a layer's other chips wait for it, under it they idle, so the
distance is what is better LOWER; the share itself and its sign are the
two counters' on the `window:` line. An engine counts the pairs only
where its layers hold a share of the experts their routers score (the
programs count the held pairs); a model that holds every expert counts
neither, and the reader finds nothing.

Moves itl_mean_ms: the held pairs are the experts' work in every decode
step and every admission.
"""

KIND = "per_layer"
LAYER = "Model step"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "itl_mean_ms"


def read(obs):
    routed = obs.counters.get("moe_pairs_routed", 0)
    share = (obs.conf or {}).get("expert_share")
    if not routed or not share:
        return None
    even = 100.0 * obs.conf["num_experts"] / share["router_width"]
    return abs(100.0 * obs.counters.get("moe_pairs_held", 0) / routed - even)
