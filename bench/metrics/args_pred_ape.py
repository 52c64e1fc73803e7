"""args_pred_ape (%): the predictor's persistent bytes per device
against the compiled step's ``argument_bytes``.

The step's arguments are the TrainState (parameters, the optimizer's
moments and float32 masters) and the batch; the predictor's matching
terms are ``param_bytes + opt_bytes + input_bytes`` of the guard's
prediction.  Gradients, activations and the output copy are not
arguments and are left out on both sides.
"""


def read(facts: dict, trace):
    pred, got = facts.get("predicted_argument_bytes"), \
        facts.get("argument_bytes")
    if not pred or not got:
        return None
    return 100.0 * abs(pred - got) / got
