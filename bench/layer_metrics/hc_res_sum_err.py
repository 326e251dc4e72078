"""The largest distance from 1 of any row or column sum of any token's
carry matrix at the window's last step (the model's ``hc_res_sum_err``
output as the kind fetched it): what the Sinkhorn iterations left. A carry
that is not doubly stochastic scales the streams' mean a block, 2 x blocks
times over a step. It FAILS THE RUN above the cell's
``expect.hc_res_sum_err_max``."""


def compute(trace, counters, run):
    err = run.get("hc_res_sum_err")
    if err is None:
        return None
    limit = run["cell"]["expect"]["hc_res_sum_err_max"]
    return err, err <= limit, "hc_res_sum_err %.3e, limit %s" % (err, limit)
