"""The benchmark's one traffic generator: agent-team workflows whose prompts
carry their shared prefixes as named blocks, read from a mix's data file.

A mix is a JSON file under ``bench/traffic/``; every number that shapes the
traffic sits there (apps, teams, block sizes, output cap, context window,
the backlog, clusters). This module turns a mix and a ``--seed`` into jobs.

Arrivals: ``{"kind": "backlog", "jobs": n}``, every job due at time 0 (an
offline batch): the window opens with more work queued than the system
holds, so it serves at capacity from its first step.

What every seed shares, and what it changes. The set of jobs (each job's
app, stage DAG, every prompt block's length, every output length, its team
and source cluster) is drawn once from the mix's ``shape_seed``. A
``--seed`` orders the backlog (which job is first in the queue) and draws
every block's token ids (and the harness draws the weights from it). So
every seed offers the same work, in another order and with other tokens.

The app templates below are a copy of Table I of the Maestro paper as the
program keeps them (``repro.data.apps``), and the prompt text and lengths
follow its trace generator (``repro.data.tracegen``): copied, so that a
change to the program cannot move the traffic.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

ROLES = ["planner", "solver", "critic", "tool_agent", "writer", "translator",
         "supervisor", "worker", "summarizer", "coder", "reviewer", "chat"]
ROLE_ID = {r: i for i, r in enumerate(ROLES)}


@dataclasses.dataclass(frozen=True)
class StageTemplate:
    role: str
    tools_available: int = 0
    p_tool: float = 0.0          # probability the stage emits a tool call
    cot: bool = False
    base_len: float = 180.0      # lognormal median of output tokens
    sigma: float = 0.6           # lognormal sigma (CoT widens it)
    tool_len: float = 45.0       # median output of a tool call
    prompt_base: int = 300       # tokens of the stage's own turn block
    deps: Tuple[int, ...] = ()
    loop: float = 0.0            # probability of repeating (geometric, <= 4)
    fanout: int = 1


def _st(role, **kw) -> StageTemplate:
    return StageTemplate(role=role, **kw)


# Table I: name -> (interactive, job-mix weight, stage templates)
APPS: Dict[str, Tuple[bool, float, Tuple[StageTemplate, ...]]] = {
    "meeting_booking": (True, 8626 / 46769, (
        _st("planner", base_len=120, prompt_base=200),
        _st("tool_agent", tools_available=3, p_tool=0.85, base_len=150,
            tool_len=40, deps=(0,), loop=0.35),
        _st("chat", base_len=90, prompt_base=350, deps=(1,)))),
    "document_writing": (False, 8319 / 46769, (
        _st("planner", base_len=250, cot=True, prompt_base=400),
        _st("writer", base_len=700, sigma=0.7, prompt_base=600, deps=(0,)),
        _st("critic", base_len=220, cot=True, deps=(1,), loop=0.4),
        _st("writer", base_len=500, prompt_base=900, deps=(2,)))),
    "news_collection": (False, 6616 / 46769, (
        _st("supervisor", base_len=200, prompt_base=250),
        _st("worker", tools_available=2, p_tool=0.7, base_len=180,
            tool_len=50, deps=(0,), fanout=4),
        _st("summarizer", base_len=420, prompt_base=1500, deps=(1,)))),
    "performance": (False, 6548 / 46769, (
        _st("tool_agent", tools_available=2, p_tool=0.8, base_len=160,
            tool_len=35, prompt_base=800),
        _st("solver", base_len=450, cot=True, prompt_base=1000, deps=(0,)),
        _st("writer", base_len=380, deps=(1,)))),
    "qa_assistant": (True, 5849 / 46769, (
        _st("solver", base_len=380, cot=True, sigma=0.8, prompt_base=500,
            tools_available=2, p_tool=0.3, tool_len=60, loop=0.3),
        _st("critic", base_len=150, deps=(0,)),
        _st("chat", base_len=260, prompt_base=700, deps=(1,)))),
    "text_translation": (False, 5124 / 46769, (
        _st("planner", base_len=80, prompt_base=150),
        _st("translator", base_len=550, sigma=0.5, prompt_base=700,
            deps=(0,), fanout=3),
        _st("critic", base_len=120, deps=(1,)))),
    "food_assistant": (True, 3334 / 46769, (
        _st("chat", base_len=110, prompt_base=200),
        _st("tool_agent", tools_available=4, p_tool=0.9, base_len=130,
            tool_len=35, deps=(0,), loop=0.45),
        _st("chat", base_len=140, deps=(1,)))),
    "travel_assistant": (True, 1543 / 46769, (
        _st("planner", base_len=220, cot=True, prompt_base=300),
        _st("tool_agent", tools_available=5, p_tool=0.9, base_len=140,
            tool_len=45, deps=(0,)),
        _st("solver", base_len=300, deps=(1,)),
        _st("tool_agent", tools_available=5, p_tool=0.85, base_len=140,
            tool_len=45, deps=(2,)),
        _st("writer", base_len=420, prompt_base=900, deps=(3,)),
        _st("chat", base_len=160, deps=(4,)))),
    "code_refactoring": (False, 810 / 46769, (
        _st("planner", base_len=300, cot=True, prompt_base=2500),
        _st("coder", base_len=900, sigma=0.8, cot=True, prompt_base=3000,
            deps=(0,), loop=0.5),
        _st("reviewer", base_len=350, cot=True, prompt_base=3500,
            deps=(1,)))),
}
APP_ID = {name: i for i, name in enumerate(APPS)}

_FILLER = ("the a of to and on for with into from about please could review "
           "data result answer item report note info step check list").split()
_COMPLEX_WORDS = ("thorough detailed comprehensive intricate elaborate "
                  "multifaceted exhaustive rigorous").split()
_SIMPLE_WORDS = "brief quick short simple concise minimal".split()
_TOPIC = ("travel menu booking flight code bug patch news market translation "
          "meeting schedule health recipe budget analysis").split()


def _prompt_text(rng, complexity: float, n_words: int) -> str:
    """Prompt text whose density of signal words carries the stage's latent
    complexity (what the program's semantic length predictor reads)."""
    total = min(160, max(16, n_words // 8))
    n_sig = int(round(complexity * 0.35 * total))
    n_simple = int(round((1.0 - complexity) * 0.15 * total))
    words = list(rng.choice(_COMPLEX_WORDS, n_sig))
    words += list(rng.choice(_SIMPLE_WORDS, n_simple))
    words += list(rng.choice(_TOPIC, 3))
    words += list(rng.choice(_FILLER, max(4, total - len(words))))
    rng.shuffle(words)
    return " ".join(words)


@dataclasses.dataclass
class Stage:
    """One LLM call: its prompt as (block key, tokens) pairs, its output
    length, and the features the program's predictor sees."""
    local: int                     # index within the job
    deps: List[int]                # local indices of prerequisites
    role: str
    blocks: List[Tuple[str, int]]
    out_len: int
    position: float
    tools_available: int
    cot: bool
    text: str

    @property
    def prompt_len(self) -> int:
        return sum(n for _, n in self.blocks)


@dataclasses.dataclass
class Job:
    app: str
    interactive: bool
    stages: List[Stage]
    team: int = 0
    src_cluster: int = 0


def _unroll(app: str, rng) -> List[Tuple[int, StageTemplate,
                                                    List[int]]]:
    """The app's template DAG with loops and fan-outs drawn: a list of
    (template index, template, local deps)."""
    out: List[Tuple[int, StageTemplate, List[int]]] = []
    last: Dict[int, List[int]] = {}
    for ti, st in enumerate(APPS[app][2]):
        deps = [d for t in st.deps for d in last.get(t, [])]
        ids = []
        for _ in range(max(st.fanout, 1)):
            reps = 1
            while st.loop > 0 and rng.random() < st.loop and reps < 4:
                reps += 1
            prev = list(deps)
            for _ in range(reps):
                out.append((ti, st, prev))
                prev = [len(out) - 1]
            ids += prev
        last[ti] = ids
    return out


def _job_shape(app: str, rng, mix: dict) -> Job:
    """One job of ``app``: block lengths and output lengths, no tokens yet.

    Every stage's prompt is its parent's blocks, then a reply block as long
    as the parent's output, a role header and a turn block of the template's
    ``prompt_base`` tokens. Where that would leave less than ``output_cap``
    positions of the ``context_tokens`` window, the stage starts a fresh
    transcript instead: the system block, the parent's reply, its role
    header and its turn (an agent framework's history cut)."""
    interactive, _, templates = APPS[app]
    sys_n, role_n = int(mix["system_tokens"]), int(mix["role_tokens"])
    cap, ctx = int(mix["output_cap"]), int(mix["context_tokens"])
    stages: List[Stage] = []
    for li, (ti, st, deps) in enumerate(_unroll(app, rng)):
        complexity = float(rng.random())
        if st.tools_available and rng.random() < st.p_tool:
            n_out = rng.lognormal(np.log(st.tool_len), 0.25)
        else:
            sig = 0.42 * st.sigma + (0.22 if st.cot else 0.0)
            n_out = rng.lognormal(
                np.log(st.base_len * (0.4 + 2.2 * complexity)), sig)
        n_out = int(np.clip(n_out, 4, cap))
        own = [(f"role:{st.role}", role_n), (f"turn:{li}", st.prompt_base)]
        if deps:
            parent = stages[deps[0]]
            reply = [(f"reply:{deps[0]}", parent.out_len)]
            blocks = list(parent.blocks) + reply + own
            if sum(n for _, n in blocks) + cap > ctx - 1:
                blocks = [("sys", sys_n)] + reply + own
        else:
            blocks = [("sys", sys_n)] + own
        n_prompt = sum(n for _, n in blocks)
        if n_prompt + cap > ctx - 1:
            raise ValueError(f"{app} stage {li}: a {n_prompt}-token prompt "
                             f"leaves no room for {cap} output tokens in a "
                             f"{ctx}-token window")
        stages.append(Stage(
            local=li, deps=list(deps), role=st.role, blocks=blocks,
            out_len=n_out,
            position=ti / max(len(templates) - 1, 1),
            tools_available=st.tools_available, cot=st.cot,
            text=_prompt_text(rng, complexity, n_prompt)))
    return Job(app=app, interactive=interactive, stages=stages)


def generate(mix: dict, seed: int) -> List[Job]:
    """The mix's backlog: every job due at time 0, with its team and source
    cluster; the same set of jobs for every ``seed``, in the seed's order."""
    arrival = mix["arrival"]
    if arrival["kind"] != "backlog":
        raise ValueError(f"unknown arrival kind {arrival['kind']!r}")
    n = int(arrival["jobs"])
    shape = np.random.default_rng([int(mix["shape_seed"]), n])
    apps = list(mix["apps"])
    w = np.array([APPS[a][1] for a in apps])
    picks = shape.choice(len(apps), size=n, p=w / w.sum())
    jobs = [_job_shape(apps[i], shape, mix) for i in picks]
    teams = shape.permutation(np.arange(n) % int(mix["teams"]))
    clusters = shape.permutation(np.arange(n)
                                 % len(mix["source_clusters"]))
    for job, team, c in zip(jobs, teams, clusters):
        job.team = int(team)
        job.src_cluster = int(mix["source_clusters"][int(c)])
    order = np.random.default_rng(_seed_words(seed) + [3]).permutation(n)
    return [jobs[i] for i in order]


def _seed_words(seed: int) -> List[int]:
    """A seed of any size (seeds may exceed 32 bits) as 32-bit words."""
    seed = int(seed)
    words = []
    while True:
        words.append(seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return words


def block_tokens(key: str, n: int, vocab: int, seed: int) -> np.ndarray:
    """Token ids of one named block: a function of the seed and the key
    alone, so equal keys give equal tokens wherever they appear."""
    h = hashlib.blake2b(f"{seed}|{key}".encode(), digest_size=8).digest()
    r = np.random.default_rng(int.from_bytes(h, "big"))
    return r.integers(0, vocab, n, dtype=np.int64)


def scoped_key(job_index: int, team: int, key: str) -> str:
    """Block keys as the prompt sees them: the system block and role headers
    are the team's, replies and turns the job's own."""
    if key == "sys" or key.startswith("role:"):
        return f"team{team}:{key}"
    return f"job{job_index}:{key}"


def prompt_tokens(job: Job, job_index: int, stage: Stage, vocab: int,
                  seed: int, cache: Optional[dict] = None) -> List[int]:
    """The stage's prompt token ids: jobs of a team open with the same
    system block and role headers."""
    cache = {} if cache is None else cache
    toks: List[np.ndarray] = []
    for key, n in stage.blocks:
        k = scoped_key(job_index, job.team, key)
        if k not in cache:
            cache[k] = block_tokens(k, n, vocab, seed)
        toks.append(cache[k])
    return [int(t) for t in np.concatenate(toks)]


def stage_count(jobs: Sequence[Job]) -> int:
    return sum(len(j.stages) for j in jobs)
