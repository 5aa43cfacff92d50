"""Device milliseconds a traced step spends in seqpool+CVM, the model, the
loss, the NaN guard, the dense optimizer and AUC, forward and backward."""

from benchmark import scope_times


def read(run):
    return scope_times.group_ms(run, "model")
