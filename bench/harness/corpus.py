"""News-style document text, copied from the program's synthetic corpus
(``repro.data.synthetic.synthetic_document``) so the benchmark's traffic
does not change when the program's generator does.

Every sentence is a template filled with a topic phrase and a weekday; a
document mixes a few topics, so same-topic sentences are redundant.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

TOPICS = [
    "the city council budget vote",
    "the championship final result",
    "the new vaccine trial data",
    "the coastal storm damage",
    "the quarterly earnings report",
    "the wildfire evacuation order",
    "the transit strike negotiations",
    "the satellite launch schedule",
]

TEMPLATES = [
    "Officials said {t} would be reviewed on {d}.",
    "Residents reacted to {t} with a mixture of relief and concern.",
    "Analysts noted that {t} had shifted expectations for {d}.",
    "A spokesperson declined to comment on {t}.",
    "Early reports about {t} were revised later on {d}.",
    "Witnesses described {t} in detail to reporters.",
    "The committee linked {t} to broader regional trends.",
    "Experts cautioned that {t} remained uncertain pending {d}.",
]
DATES = ["Monday", "Tuesday", "Wednesday", "Thursday", "Friday"]


def document(seed: int, n_sentences: int) -> List[str]:
    """``n_sentences`` sentences of one article, a pure function of ``seed``."""
    rng = np.random.default_rng(seed)
    doc_topics = rng.choice(
        len(TOPICS), size=min(len(TOPICS), max(2, n_sentences // 6)), replace=False
    )
    sents = []
    for _ in range(n_sentences):
        t = TOPICS[int(rng.choice(doc_topics))]
        tpl = TEMPLATES[int(rng.integers(len(TEMPLATES)))]
        d = DATES[int(rng.integers(len(DATES)))]
        sents.append(tpl.format(t=t, d=d))
    return sents


def sentence_bytes_range() -> Tuple[int, int]:
    """Fewest and most UTF-8 bytes one generated sentence can have."""
    sizes = [len(tpl.format(t=t, d=d).encode("utf-8"))
             for tpl in TEMPLATES for t in TOPICS for d in DATES]
    return min(sizes), max(sizes)
