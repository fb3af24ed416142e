"""``journal_ratio.py``, for the ratios that split the window's wall time by
what the host waited for: read only where the program's tick journal keeps
``device_wait_ns`` — the wait for a readback's last result, apart from the
copies (``readback_ns``) — and None where it does not.  An older program's
``readback_ns`` holds both, so a "host" ratio read there would be nearly
the whole window, and ``journal_ratio.py`` itself raises on a field the
journal does not have.  Same arguments, same kept records, same log line.
"""

import functools
import importlib.util
import os


@functools.cache
def _journal_ratio():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "journal_ratio.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark._file_journal_ratio_py", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(ctx, **args):
    from flexflow_tpu.obs import journal as J

    if "device_wait_ns" not in J.FIELDS:
        return None
    return _journal_ratio().read(ctx, **args)
