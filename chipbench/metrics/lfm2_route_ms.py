"""Per call: device time of the routing round the held experts, by the
program's scope map (``scope_trace``'s piece ``route``): the routers' products,
sigmoids and top-4, the sorts, the gathers of a window's rows and the sums back
into their tokens (scopes ``moe.route`` and ``moe.combine``), and what the
expert layer does outside its scopes (counts, the windows' branch); all passes."""

from chipbench import lfm2_trace


def read(reading):
    return lfm2_trace.piece_ms(reading, "route")
