"""Flagship model families beyond the vision zoo (bench configs #2-#5):
BERT (GluonNLP parity), LSTM LM (PTB), Transformer NMT (Sockeye parity),
SSD detection (GluonCV parity); decoders for ``serve.GenerativeServer``:
GPT-2 (``gpt``), a Cohere2-MoE share (``cohere_moe``: parallel block,
grouped K/V heads, window rings beside full pages, routed experts), a
Brumby style decoder (``brumby``: power retention, whose cache is a
recurrent state of fixed size a slot) and a DeepSeek-V3 lineage share
(``latent_moe``: latent attention, whose cache is one compressed row a
position, a dense layer and then routed experts with a scaled sum) and a
Nemotron-H style hybrid share (``nemotron_h``: Mamba-2 mixers, whose cache
is a snapshot of state, beside an attention layer with K/V pages, and
latent experts)."""
from . import bert  # noqa: F401
from . import lstm_lm  # noqa: F401
from . import transformer  # noqa: F401
from . import ssd  # noqa: F401
from . import faster_rcnn  # noqa: F401
from . import gpt  # noqa: F401
from . import cohere_moe  # noqa: F401
from . import brumby  # noqa: F401
from . import latent_moe  # noqa: F401
from . import nemotron_h  # noqa: F401
from . import yolo  # noqa: F401
from . import fcn  # noqa: F401
from . import pose  # noqa: F401
