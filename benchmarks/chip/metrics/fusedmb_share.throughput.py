"""Share (%) of the timed steps' device time spent under the
``fusedmb<i>`` scopes, Pallas kernels and XLA glue together
(``scopes.py``): how much of the step the Fused-MBConv blocks take.  None
where the program names no such scope."""

from scopes import step_scope_time


def read(reading):
    st = step_scope_time(reading)
    if st is None or not any(sc.startswith("fusedmb") for sc, _ in st.pairs):
        return None
    return st.share(lambda scope, kernel: scope.startswith("fusedmb"))
