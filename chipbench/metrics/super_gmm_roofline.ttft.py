"""The super-GMM's share of its roofline, %, in a cell judged on TTFT."""
from chipbench.readers import super_gmm_roofline


def read(run):
    return super_gmm_roofline(run)
