"""Share (%) of the timed steps' device time spent under the ``stem`` and
``head`` scopes: the stem convolution and activation with the images'
relayout, the head convolution, pool, FC and classifier (``scopes.py``)."""

from scopes import is_block, step_scope_time


def read(reading):
    st = step_scope_time(reading)
    return None if st is None else st.share(
        lambda scope, kernel: not is_block(scope))
