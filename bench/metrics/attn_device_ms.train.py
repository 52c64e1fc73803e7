"""attn_device_ms.train (ms/step): device time of the train step's leaf
ops under the named scope ``attn``, per ``step`` span of the traced
window, averaged over the devices.

The driver maps the compiled step's instructions to scopes at set-up
(``facts["op_scopes"]``, ``span_reduce.op_scopes``); ``while``,
``conditional`` and ``call`` are left out by their opcode, since
their bodies' ops are events of their own.
"""

import span_reduce


def read(facts: dict, trace):
    return span_reduce.scope_ms_per_step(facts, trace, "attn")
