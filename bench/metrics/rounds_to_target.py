"""Rounds the program needs to bring a federation's criterion
``||sum_i grad f_i(mean_i x_i)||^2`` to the paper's threshold: the
hitting round of the criterion history the program reports, averaged
over the cell's fixed set of federations."""


def read(r):
    return r.get("rounds_to_target")
