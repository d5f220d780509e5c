"""Operations and bytes of one step's expert feed-forward (SwiGLU experts
behind a top-k router), from what the router did: ``assignments`` (token x
expert pairs) and ``experts_touched`` (experts that got at least one
token), each summed over the expert layers of the step.

The least work of the layer, whatever implements it: the three matrices of
every expert that got a token read ONCE (an expert nobody chose is not
read), each assignment's input row read and output row written once, and
two operations a weight for each assignment.  Not the program's: a grouped
matmul that pads its groups multiplies the padding too, one that walks a
group in several blocks reads its expert's weights once a block, and the
router, the sort, the gathers and the combine are no part of it; all of
that shows as a lower share."""


def expert_params(hidden, expert_width):
    return 3 * hidden * expert_width


def flops(assignments, hidden, expert_width):
    return 2.0 * assignments * expert_params(hidden, expert_width)


def bytes_moved(assignments, experts_touched, hidden, expert_width,
                weight_itemsize, act_itemsize):
    return (experts_touched * expert_params(hidden, expert_width)
            * float(weight_itemsize)
            + assignments * 2.0 * hidden * act_itemsize)
