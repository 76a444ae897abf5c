"""Roofline share of the recurrence under the ``rnn_lstm`` scope: the
input projections and the time loop of every layer, forward and backward."""
import trace_reduce


def read(ctx):
    return trace_reduce.roofline_share(ctx, "rnn_lstm")
