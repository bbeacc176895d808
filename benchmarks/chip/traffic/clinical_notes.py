"""Seeded clinical-note traffic for the chip benchmark.

A vectorised copy of the corpus protocol the paper evaluates on
(arXiv:1704.05617 §7.1, §9.1, §10): templated clinical notes whose
sections are filled from small vocabularies, with the history and the
plan pasted a second time (template copy-paste), plus near-duplicates
made by changing 0-20% of a note's words.  The templates, vocabularies,
fill ranges and perturbation are those of the repository's corpus
generator; the draws are made for a whole batch of notes at once, so a
16,384-note corpus takes a fraction of a second instead of tens.

Everything is drawn from a ``numpy.random.Generator`` built from the
run's seed, so the same seed gives the same notes and duplicates.
Where a quantity only sets how much work a run does (the fraction of
words a duplicate changes), the draw is stratified: every seed gets the
same set of values in another order, so seeds change which notes are
sent, not how much work there is.

This module is the benchmark's own yardstick: it imports nothing of the
system under test.
"""
from __future__ import annotations

import string

import numpy as np

SECTIONS = (
    "CHIEF COMPLAINT : {complaint} .",
    "HISTORY OF PRESENT ILLNESS : The patient is a {age} year old "
    "{sex} presenting with {complaint} for the past {num} days . "
    "Symptoms include {sym1} and {sym2} . Denies {sym3} .",
    "PAST MEDICAL HISTORY : {pmh1} , {pmh2} , status post {procedure} "
    "in {year} .",
    "MEDICATIONS : {med1} {dose1} mg daily , {med2} {dose2} mg twice "
    "daily , {med3} as needed .",
    "ALLERGIES : {allergy} .",
    "PHYSICAL EXAM : Vital signs temperature {temp} pulse {pulse} "
    "blood pressure {bp1} over {bp2} . {exam} .",
    "ASSESSMENT AND PLAN : {assessment} . Will start {med1} and follow "
    "up in {num} weeks . Patient counseled on {counsel} .",
    "LABS : sodium {lab1} potassium {lab2} creatinine {lab3} glucose "
    "{lab4} white count {lab5} .",
)

VOCAB = {
    "complaint": ["chest pain", "shortness of breath", "abdominal pain",
                  "headache", "dizziness", "fatigue", "back pain",
                  "palpitations", "fever", "cough"],
    "sex": ["male", "female"],
    "sym1": ["nausea", "vomiting", "diaphoresis", "chills", "weakness"],
    "sym2": ["radiation to the left arm", "photophobia", "orthopnea",
             "dysuria", "myalgias"],
    "sym3": ["fever", "chills", "weight loss", "night sweats", "syncope"],
    "pmh1": ["hypertension", "diabetes mellitus type 2", "asthma",
             "atrial fibrillation", "hyperlipidemia"],
    "pmh2": ["chronic kidney disease", "coronary artery disease",
             "obstructive sleep apnea", "hypothyroidism", "anemia"],
    "procedure": ["appendectomy", "cholecystectomy", "cabg",
                  "total knee replacement", "hernia repair"],
    "med1": ["lisinopril", "metformin", "atorvastatin", "amlodipine",
             "metoprolol"],
    "med2": ["aspirin", "omeprazole", "levothyroxine", "gabapentin",
             "furosemide"],
    "med3": ["acetaminophen", "ibuprofen", "ondansetron", "albuterol"],
    "allergy": ["no known drug allergies", "penicillin", "sulfa drugs",
                "codeine", "latex"],
    "exam": ["lungs clear to auscultation bilaterally",
             "regular rate and rhythm no murmurs",
             "abdomen soft nontender nondistended",
             "no lower extremity edema",
             "alert and oriented times three"],
    "assessment": ["acute coronary syndrome ruled out",
                   "community acquired pneumonia",
                   "urinary tract infection",
                   "exacerbation of chronic condition",
                   "dehydration with electrolyte abnormalities"],
    "counsel": ["medication compliance", "smoking cessation",
                "dietary modification", "warning signs requiring return"],
}

# Integer fills: [low, high) as numpy's ``integers`` draws them.
INT_FILLS = {
    "age": (18, 95), "num": (1, 14), "year": (1990, 2016),
    "temp": (97, 103), "pulse": (55, 120), "bp1": (95, 180),
    "bp2": (55, 110), "lab1": (130, 148), "lab4": (70, 260),
}
CHOICE_FILLS = {"dose1": (5, 10, 20, 40), "dose2": (25, 50, 100)}
# One-decimal fills: uniform on [low, high), rounded to 0.1.
DECIMAL_FILLS = {"lab2": (3.2, 5.4), "lab3": (0.6, 3.0), "lab5": (4.0, 15.0)}

# Replacement words of the perturbation: the first word of every
# vocabulary phrase, with the repeats the phrase lists have.
REPLACEMENTS = [w.split()[0] for v in VOCAB.values() for w in v]

_FIELDS = [[f for _, f, _, _ in string.Formatter().parse(s) if f]
           for s in SECTIONS]


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """A generator for one named stream of one run's seed.

    Any whole number is a valid seed (negative ones wrap modulo 2**64);
    different streams of one seed are independent.
    """
    tag = int.from_bytes(stream.encode("utf-8"), "little")
    return np.random.default_rng([int(seed) % (1 << 64), tag])


def _fill_column(field: str, n: int, rng: np.random.Generator) -> list:
    if field in VOCAB:
        words = VOCAB[field]
        return [words[i] for i in rng.integers(0, len(words), n)]
    if field in INT_FILLS:
        lo, hi = INT_FILLS[field]
        return rng.integers(lo, hi, n).tolist()
    if field in CHOICE_FILLS:
        opts = CHOICE_FILLS[field]
        return [opts[i] for i in rng.integers(0, len(opts), n)]
    lo, hi = DECIMAL_FILLS[field]
    return [f"{v:.1f}" for v in rng.uniform(lo, hi, n)]


def make_notes(n: int, rng: np.random.Generator) -> list[str]:
    """``n`` templated notes: eight sections, each filled independently,
    then the history and the plan pasted again at the end."""
    if n <= 0:
        return []
    parts = []
    for sec, fields in zip(SECTIONS, _FIELDS):
        cols = {f: _fill_column(f, n, rng) for f in dict.fromkeys(fields)}
        parts.append([sec.format(**{f: cols[f][i] for f in cols})
                      for i in range(n)])
    hpi, plan = parts[1], parts[-2]
    return [" ".join(p[i] for p in parts) + " " + hpi[i] + " " + plan[i]
            for i in range(n)]


def perturb(text: str, frac: float, rng: np.random.Generator) -> str:
    """Change ``int(words * frac)`` distinct words of ``text`` to the
    first word of a random vocabulary phrase (paper §9.1, §10)."""
    words = text.split()
    k = int(len(words) * frac)
    if k:
        idx = rng.choice(len(words), size=k, replace=False)
        for i, r in zip(idx, rng.integers(0, len(REPLACEMENTS), k)):
            words[i] = REPLACEMENTS[r]
    return " ".join(words)


def stratified(n: int, low: float, high: float,
               rng: np.random.Generator) -> np.ndarray:
    """``n`` values spread evenly over [low, high), in a random order."""
    q = (np.arange(n) + 0.5) / max(n, 1)
    return low + (high - low) * rng.permutation(q)


def near_duplicates(sources: list[str], frac_low: float, frac_high: float,
                    rng: np.random.Generator) -> list[str]:
    """One near-duplicate of each source, with a stratified fraction of
    its words changed."""
    fracs = stratified(len(sources), frac_low, frac_high, rng)
    return [perturb(s, float(f), rng) for s, f in zip(sources, fracs)]


def corpus_chunk(pool: list[str], n: int, dup_share: float,
                 frac_low: float, frac_high: float,
                 rng: np.random.Generator) -> list[str]:
    """One ingest chunk of ``n`` notes by the §10 protocol.

    ``n - round(n * dup_share)`` fresh notes are appended to ``pool``
    (the fresh notes generated so far); the rest are near-duplicates of
    notes drawn uniformly from the updated pool.  Duplicates sit at
    random positions in the chunk.
    """
    n_dup = int(round(n * dup_share))
    fresh = make_notes(n - n_dup, rng)
    pool.extend(fresh)
    src = rng.integers(0, len(pool), n_dup)
    dups = near_duplicates([pool[i] for i in src], frac_low, frac_high, rng)
    chunk = fresh + dups
    return [chunk[i] for i in rng.permutation(len(chunk))]
