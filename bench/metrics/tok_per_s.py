"""Tokens the label owner served in the window, per second: every reply
(prompt step or generated token) that reached its session in the window,
over the window's length."""


def read(run):
    return float(run.replies_between(run.t_open, run.t_close).sum()
                 / run.seconds)
