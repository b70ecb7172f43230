from .synthetic import (DatasetSpec, SIFT_LIKE, DEEP_LIKE, TTI_LIKE,  # noqa: F401
                        make_dataset, make_dataset_chunked)
