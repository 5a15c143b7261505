"""Model zoo — the BASELINE.json configs rebuilt on the static-graph API
(ref model definitions: models-repo PaddleCV image_classification /
PaddleNLP BERT, and the reference's tests/book models)."""

from . import mnist      # noqa: F401
from . import resnet     # noqa: F401
from . import bert       # noqa: F401
from . import decoder    # noqa: F401
from . import decoder_lm  # noqa: F401
from . import transformer  # noqa: F401
from . import ernie      # noqa: F401
from . import word2vec   # noqa: F401
from . import se_resnext  # noqa: F401
