"""A reference module without ``decision_paths``, for the harness's
tests only: a configuration that names it must fail to load."""


def make_pool(key, c, size):
    raise NotImplementedError


def deploy_params(key, c, bits=None):
    raise NotImplementedError


def forward(params, c, inputs, *, lfsr_seed, mode, bits=None, cache=None):
    raise NotImplementedError


def cbr_layers(c):
    raise NotImplementedError


def mapping_flops(c):
    raise NotImplementedError
