"""Per call: device time of the routing round the held experts, by the
program's scope map (``scope_trace``'s piece ``route``): the routers' products,
sigmoids and top-4, the sorts, the gathers of a window's rows and the sums back
into their tokens, and what the expert layer does outside its scopes; all passes."""

from chipbench import glm_trace


def read(reading):
    return glm_trace.tag_ms(reading, "route")
