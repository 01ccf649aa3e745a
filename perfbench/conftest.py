"""One BLAS thread for the benchmark's tests, as for its runs
(``run.py``): the reference's many small dense solves gain nothing from a
pool of threads, and lose much where other load holds the cores."""

import pytest
import threadpoolctl


@pytest.fixture(autouse=True, scope="module")
def one_blas_thread():
    with threadpoolctl.threadpool_limits(limits=1, user_api="blas"):
        yield
