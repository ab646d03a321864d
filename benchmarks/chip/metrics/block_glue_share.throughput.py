"""Share (%) of the timed steps' device time spent in operations that are
not Pallas kernels but belong to a block: the pads, slices, residual adds
and SE ops under an ``mbconv<i>`` or ``fusedmb<i>`` scope, wherever in
the step they run (``scopes.py``)."""

from scopes import XLA, is_block, step_scope_time


def read(reading):
    st = step_scope_time(reading)
    return None if st is None else st.share(
        lambda scope, kernel: is_block(scope) and kernel == XLA)
