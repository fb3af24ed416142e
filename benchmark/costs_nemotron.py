"""Operations and bytes of what a Mamba-2 / routed-expert hybrid
(``nemotron_h``) adds to a decode step, from its shapes and from the
routing's own counts (the companion of costs.py, same rule: the least the
algorithm must do, whatever implements it, so a roofline share computed from
these cannot be flattered by wasted work).
"""

from benchmark.reference import nemotron_h as arch


def routed_decode_cost(experts_visited, pairs, hf, weight_bytes=2,
                       io_bytes=2):
    """The held experts' two GEMMs for ``pairs`` (row, choice) pairs that
    visit ``experts_visited`` (expert, layer, step) triples: each visited
    expert's up and down matrix (``2 x hidden x width`` elements) streamed
    once, each pair's row in and out (``hidden`` elements each way; the
    ``width`` between the two GEMMs can stay on chip); ``4 x hidden x
    width`` operations a pair."""
    d, f = hf["hidden_size"], hf["moe_intermediate_size"]
    nbytes = (experts_visited * 2 * d * f * weight_bytes
              + pairs * 2 * d * io_bytes)
    return pairs * 4 * d * f, nbytes


def mamba2_decode_cost(context_lens, hf, state_bytes=4, io_bytes=2):
    """The Mamba-2 layers' state update and read-out for
    ``len(context_lens)`` decode rows (the contexts do not matter: the state
    is of fixed size): per row and ``M`` layer the ``heads x head size x
    state`` float32 state read once and written once, the conv's tail
    (``kernel - 1`` rows of ``conv_dim``) read and written, ``xBC`` and
    ``dt`` in and ``y`` out; 5 operations a state element (the decay's
    product, the rank-one update's product and sum, the read-out's product
    and sum)."""
    layers = arch.layer_kinds(hf).count(arch.MAMBA)
    h, p, n = hf["mamba_num_heads"], hf["mamba_head_dim"], hf["ssm_state_size"]
    conv_dim = h * p + 2 * hf["n_groups"] * n
    tail = (hf.get("conv_kernel", 4) - 1) * conv_dim
    rows = len(context_lens)
    state = h * p * n
    per_row = (2 * state * state_bytes
               + (2 * tail + 2 * conv_dim + h + h * p) * io_bytes)
    return layers * rows * 5 * state, layers * rows * per_row
