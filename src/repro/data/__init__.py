from .synthetic import (  # noqa: F401
    gaussian_mixture_sampler,
    labelled_images,
    lm_batch_iterator,
    procedural_images,
    synthetic_lm_batch,
)
