"""A fake lichess fishnet server for integration tests.

Serves the JSON protocol documented in the reference's doc/protocol.md
(acquire / analysis / move / abort / status / key). The reference has no
such test double — SURVEY.md §4 calls out creating one as the first piece
of test infrastructure the new framework must add.

Queue semantics mimic lila: jobs are handed out on acquire, re-queued if
aborted, and recorded on submission. ``slow=true`` clients only get
system-queue jobs.
"""

from __future__ import annotations

import itertools
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from aiohttp import web

VALID_KEY = "TESTKEY"


@dataclass
class FakeJob:
    body: dict
    user_queue: bool = True
    acquired_by: Optional[str] = None
    #: Monotonic time of the LAST handout (0 = never handed). Drives the
    #: server-side reassignment sweep (``FakeLichess.reassign_after``).
    last_handed: float = 0.0


@dataclass
class FleetUnit:
    """Per-work-unit audit record: every handout to any process, every
    completion, every time the server took it back."""

    #: (monotonic time, process key) for each handout.
    handouts: List = field(default_factory=list)
    completions: int = 0
    completed_by: Optional[str] = None
    #: Times the server re-queued it (client abort or timeout sweep).
    requeues: List = field(default_factory=list)  # (time, reason)
    #: Stale submissions the server refused (404): the sweep had
    #: already re-handed the unit to another process, or it was
    #: already completed. Fencing is what keeps completions exactly
    #: once when a partitioned-but-alive process's submit finally
    #: lands after its work was given away.
    fences: List = field(default_factory=list)  # (time, proc)


class FleetLedger:
    """Server-side exactly-once audit across PROCESSES — the cross-
    process twin of ``resilience/accounting.py`` (which lives inside one
    client and dies with it). Tracks every work unit the server ever
    handed to any process and answers, after kills / partitions /
    drains: was anything LOST (handed out, never completed, no longer
    queued for reassignment) or DUPLICATED (completed more than once)?

    Mutated only from the server's single event loop; readers take
    snapshots after the run.
    """

    def __init__(self) -> None:
        self.units: Dict[str, FleetUnit] = {}
        #: Successful-handout timestamps per process key — recovery-time
        #: measurement: first acquire after a restart marks the process
        #: back at steady state.
        self.acquires_by_proc: Dict[str, List[float]] = {}

    def record_handed(self, work_id: str, proc: str) -> None:
        now = time.monotonic()
        unit = self.units.setdefault(work_id, FleetUnit())
        unit.handouts.append((now, proc))
        self.acquires_by_proc.setdefault(proc, []).append(now)

    def record_completed(self, work_id: str, proc: str) -> None:
        unit = self.units.setdefault(work_id, FleetUnit())
        unit.completions += 1
        unit.completed_by = proc

    def record_fenced(self, work_id: str, proc: str) -> None:
        unit = self.units.setdefault(work_id, FleetUnit())
        unit.fences.append((time.monotonic(), proc))

    def record_requeued(self, work_id: str, reason: str) -> None:
        unit = self.units.setdefault(work_id, FleetUnit())
        unit.requeues.append((time.monotonic(), reason))

    def report(self, open_ids=()) -> Dict[str, object]:
        """The audit. ``open_ids``: work ids still queued on the server
        (awaiting reassignment) — handed-but-uncompleted units among
        them are in flight, not lost."""
        open_set = set(open_ids)
        handed = [w for w, u in self.units.items() if u.handouts]
        lost = [
            w for w, u in self.units.items()
            if u.handouts and u.completions == 0 and w not in open_set
        ]
        duplicated = [w for w, u in self.units.items() if u.completions > 1]
        reassigned = [w for w, u in self.units.items() if u.requeues]
        multi_proc = [
            w for w, u in self.units.items()
            if len({p for _, p in u.handouts}) > 1
        ]
        return {
            "handed": len(handed),
            "completed": sum(
                1 for u in self.units.values() if u.completions > 0
            ),
            "lost": sorted(lost),
            "duplicated": sorted(duplicated),
            "reassigned": len(reassigned),
            "fenced": sum(len(u.fences) for u in self.units.values()),
            "multi_proc": sorted(multi_proc),
            "clean": not lost and not duplicated,
        }

    def assert_clean(self, open_ids=()) -> None:
        report = self.report(open_ids)
        assert report["clean"], (
            f"fleet ledger dirty: lost={report['lost']} "
            f"duplicated={report['duplicated']}"
        )


@dataclass
class FakeLichess:
    """In-memory job queue + recorders, exposed over HTTP."""

    jobs: List[FakeJob] = field(default_factory=list)
    analyses: Dict[str, List[dict]] = field(default_factory=dict)
    #: How many times a COMPLETED analysis was received per work id —
    #: the server-side half of the exactly-once assertion (the
    #: ``analyses`` dict alone would silently hide duplicates).
    analysis_submission_counts: Dict[str, int] = field(default_factory=dict)
    progress_reports: Dict[str, List[dict]] = field(default_factory=dict)
    moves: Dict[str, dict] = field(default_factory=dict)
    aborted: List[str] = field(default_factory=list)
    acquire_count: int = 0
    reject_with: Optional[int] = None  # force an HTTP status on acquire
    #: Fail the next N completed-analysis submissions with HTTP 500
    #: (exercises the client's submit retry + circuit breaker).
    fail_submits: int = 0
    status_supported: bool = True
    abort_supported: bool = True
    require_key: bool = True
    #: Saturating load generator: keep at least this many unacquired
    #: system-queue analysis jobs in the queue at every acquire — the
    #: queue never drains, which is what "4x saturating load" means for
    #: tests/test_overload.py. 0 disables (default: finite queue as before).
    auto_refill: int = 0
    #: With auto_refill active, every Nth synthesized job is a best-move
    #: job so the latency lane sees traffic during saturation. 0 = never.
    refill_move_every: int = 0
    #: Cap on total synthesized jobs, so a shedding client can't make the
    #: generator spin forever. None = unbounded.
    refill_limit: Optional[int] = None
    refill_count: int = 0
    #: Latency bookkeeping (monotonic clock): when a job was handed out
    #: on acquire, when its first progress/analysis report arrived, when
    #: the completed analysis landed, and when a move was submitted.
    handed_at: Dict[str, float] = field(default_factory=dict)
    first_report_at: Dict[str, float] = field(default_factory=dict)
    completed_at: Dict[str, float] = field(default_factory=dict)
    move_done_at: Dict[str, float] = field(default_factory=dict)
    #: Generated work-id prefix. Override when one test (or soak phase)
    #: runs several servers against one shared ledger: each server's
    #: counter restarts at 0, so identical prefixes would collide.
    work_id_prefix: str = "wk"
    #: Cross-process exactly-once audit (cluster tests).
    #: Always recorded — it is pure bookkeeping on existing handlers.
    fleet: FleetLedger = field(default_factory=FleetLedger)
    #: Server-side reassignment timeout (seconds): an acquired job not
    #: completed within this window goes back in the queue for another
    #: process — lila's recovery primitive (doc/protocol.md), and the
    #: only thing that rescues a SIGKILLed process's work. None = no
    #: sweep (single-process tests keep the old semantics).
    reassign_after: Optional[float] = None
    _counter: itertools.count = field(default_factory=itertools.count)

    # -- job injection (test side) ---------------------------------------

    def add_analysis_job(
        self,
        moves: str = "e2e4 e7e5",
        position: str = "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1",
        variant: str = "standard",
        skip_positions: Optional[List[int]] = None,
        nodes: int = 5000,
        game_id: Optional[str] = None,
        multipv: Optional[int] = None,
        depth: Optional[int] = None,
        user_queue: bool = False,
        work_id: Optional[str] = None,
    ) -> str:
        work_id = work_id or f"{self.work_id_prefix}{next(self._counter):06d}"
        work = {
            "type": "analysis",
            "id": work_id,
            "nodes": {"sf15": nodes, "sf14": nodes, "classical": nodes * 2},
            "timeout": 7000,
        }
        if multipv is not None:
            work["multipv"] = multipv
        if depth is not None:
            work["depth"] = depth
        body = {
            "work": work,
            "game_id": game_id or "",
            "position": position,
            "variant": variant,
            "moves": moves,
            "skipPositions": skip_positions or [],
        }
        self.jobs.append(FakeJob(body=body, user_queue=user_queue))
        return work_id

    def add_move_job(
        self,
        moves: str = "",
        position: str = "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1",
        level: int = 5,
        clock: Optional[dict] = None,
        variant: str = "standard",
        work_id: Optional[str] = None,
    ) -> str:
        work_id = work_id or f"{self.work_id_prefix}{next(self._counter):06d}"
        work: dict = {"type": "move", "id": work_id, "level": level}
        if clock:
            work["clock"] = clock
        body = {
            "work": work,
            "game_id": "",
            "position": position,
            "variant": variant,
            "moves": moves,
        }
        self.jobs.append(FakeJob(body=body, user_queue=False))
        return work_id

    def _refill(self) -> None:
        """Top the queue back up to ``auto_refill`` unacquired jobs."""
        if self.auto_refill <= 0:
            return
        pending = sum(1 for j in self.jobs if j.acquired_by is None)
        while pending < self.auto_refill:
            if self.refill_limit is not None and self.refill_count >= self.refill_limit:
                return
            self.refill_count += 1
            if (
                self.refill_move_every > 0
                and self.refill_count % self.refill_move_every == 0
            ):
                self.add_move_job()
            else:
                self.add_analysis_job()
            pending += 1

    # -- handlers --------------------------------------------------------

    def _check_auth(self, request: web.Request, body: Optional[dict]) -> bool:
        if not self.require_key:
            return True
        auth = request.headers.get("Authorization", "")
        if auth == f"Bearer {VALID_KEY}":
            return True
        if body and body.get("fishnet", {}).get("apikey") == VALID_KEY:
            return True
        return False

    def _reassign_stale(self) -> None:
        """The server-side reassignment sweep: acquired jobs older than
        ``reassign_after`` go back in the queue. Run at every acquire —
        the moment another process shows up hungry is exactly when a
        dead process's work should become available again."""
        if self.reassign_after is None:
            return
        now = time.monotonic()
        for job in self.jobs:
            if (
                job.acquired_by is not None
                and now - job.last_handed > self.reassign_after
            ):
                self.fleet.record_requeued(job.body["work"]["id"], "timeout")
                job.acquired_by = None

    async def handle_acquire(self, request: web.Request) -> web.Response:
        self.acquire_count += 1
        body = await request.json()
        if self.reject_with:
            return web.Response(status=self.reject_with, text="rejected by test")
        if not self._check_auth(request, body):
            return web.Response(status=401, text="unknown key")
        slow = request.query.get("slow") == "true"
        self._reassign_stale()
        self._refill()
        for job in self.jobs:
            if job.acquired_by is None and not (slow and job.user_queue):
                proc = body.get("fishnet", {}).get("apikey", "?")
                job.acquired_by = proc
                job.last_handed = time.monotonic()
                self.handed_at.setdefault(job.body["work"]["id"], time.monotonic())
                self.fleet.record_handed(job.body["work"]["id"], proc)
                return web.json_response(job.body, status=202)
        return web.Response(status=204)

    def _fence(self, work_id: str, body: dict) -> Optional[web.Response]:
        """Exactly-once enforcement: refuse (404, like lila for work it
        no longer knows) a completion from a process that is not the
        unit's CURRENT holder — the timeout sweep re-handed it, or it
        was already completed. Without this, a partitioned-but-alive
        process's delayed submit lands after the reassignee's and the
        unit double-completes. A requeued-but-unclaimed unit still
        accepts its original holder's late submit (the sweep was
        premature; nobody else did the work)."""
        proc = body.get("fishnet", {}).get("apikey", "?")
        job = next(
            (j for j in self.jobs if j.body["work"]["id"] == work_id), None
        )
        stale = (
            job is None
            if work_id in self.fleet.units
            else False
        ) or (
            job is not None
            and job.acquired_by is not None
            and job.acquired_by != proc
        )
        if stale:
            self.fleet.record_fenced(work_id, proc)
            return web.Response(status=404, text="unknown work")
        return None

    async def handle_analysis(self, request: web.Request) -> web.Response:
        work_id = request.match_info["id"]
        body = await request.json()
        if not self._check_auth(request, body):
            return web.Response(status=401)
        parts = body.get("analysis", [])
        self.first_report_at.setdefault(work_id, time.monotonic())
        # Lila quirk: a report whose first part is null is a progress
        # report, not a completed analysis (reference src/queue.rs:686-697).
        if parts and parts[0] is None:
            self.progress_reports.setdefault(work_id, []).append(body)
        else:
            fenced = self._fence(work_id, body)
            if fenced is not None:
                return fenced
            if self.fail_submits > 0:
                self.fail_submits -= 1
                return web.Response(status=500, text="injected submit failure")
            self.analysis_submission_counts[work_id] = (
                self.analysis_submission_counts.get(work_id, 0) + 1
            )
            self.analyses[work_id] = body
            self.completed_at.setdefault(work_id, time.monotonic())
            self.fleet.record_completed(
                work_id, body.get("fishnet", {}).get("apikey", "?")
            )
            self.jobs = [j for j in self.jobs if j.body["work"]["id"] != work_id]
        return web.Response(status=204)

    async def handle_move(self, request: web.Request) -> web.Response:
        work_id = request.match_info["id"]
        body = await request.json()
        if not self._check_auth(request, body):
            return web.Response(status=401)
        fenced = self._fence(work_id, body)
        if fenced is not None:
            return fenced
        self.moves[work_id] = body
        self.move_done_at.setdefault(work_id, time.monotonic())
        proc = body.get("fishnet", {}).get("apikey", "?")
        self.fleet.record_completed(work_id, proc)
        self.jobs = [j for j in self.jobs if j.body["work"]["id"] != work_id]
        # Chained acquire (202 with next job) when available.
        for job in self.jobs:
            if job.acquired_by is None and job.body["work"]["type"] == "move":
                job.acquired_by = proc
                job.last_handed = time.monotonic()
                self.handed_at.setdefault(job.body["work"]["id"], time.monotonic())
                self.fleet.record_handed(job.body["work"]["id"], proc)
                return web.json_response(job.body, status=202)
        return web.Response(status=204)

    async def handle_abort(self, request: web.Request) -> web.Response:
        if not self.abort_supported:
            return web.Response(status=404)
        work_id = request.match_info["id"]
        body = await request.json()
        if not self._check_auth(request, body):
            return web.Response(status=401)
        self.aborted.append(work_id)
        for job in self.jobs:
            if job.body["work"]["id"] == work_id:
                if job.acquired_by is not None:
                    self.fleet.record_requeued(work_id, "abort")
                job.acquired_by = None  # re-queue
        return web.Response(status=204)

    async def handle_status(self, request: web.Request) -> web.Response:
        if not self.status_supported:
            return web.Response(status=404)
        user = [j for j in self.jobs if j.user_queue and j.acquired_by is None]
        system = [j for j in self.jobs if not j.user_queue and j.acquired_by is None]
        return web.json_response(
            {
                "analysis": {
                    "user": {"acquired": 0, "queued": len(user), "oldest": 0},
                    "system": {"acquired": 0, "queued": len(system), "oldest": 0},
                }
            }
        )

    async def handle_key(self, request: web.Request) -> web.Response:
        auth = request.headers.get("Authorization", "")
        if auth == f"Bearer {VALID_KEY}":
            return web.Response(status=200)
        return web.Response(status=401)

    async def handle_key_legacy(self, request: web.Request) -> web.Response:
        if request.match_info["key"] == VALID_KEY:
            return web.Response(status=200)
        return web.Response(status=404)

    def fleet_report(self) -> Dict[str, object]:
        """The fleet-ledger audit, with still-queued jobs counted as in
        flight (awaiting reassignment), not lost."""
        open_ids = [j.body["work"]["id"] for j in self.jobs]
        return self.fleet.report(open_ids)

    def app(self) -> web.Application:
        app = web.Application()
        app.router.add_post("/fishnet/acquire", self.handle_acquire)
        app.router.add_post("/fishnet/analysis/{id}", self.handle_analysis)
        app.router.add_post("/fishnet/move/{id}", self.handle_move)
        app.router.add_post("/fishnet/abort/{id}", self.handle_abort)
        app.router.add_get("/fishnet/status", self.handle_status)
        app.router.add_get("/fishnet/key", self.handle_key)
        app.router.add_get("/fishnet/key/{key}", self.handle_key_legacy)
        return app


class FakeServer:
    """Async context manager running a FakeLichess on an ephemeral port."""

    def __init__(self, lichess: Optional[FakeLichess] = None) -> None:
        self.lichess = lichess or FakeLichess()
        self.endpoint = ""
        self._runner: Optional[web.AppRunner] = None

    async def __aenter__(self) -> "FakeServer":
        self._runner = web.AppRunner(self.lichess.app())
        await self._runner.setup()
        site = web.TCPSite(self._runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]  # type: ignore[union-attr]
        self.endpoint = f"http://127.0.0.1:{port}/fishnet"
        return self

    async def __aexit__(self, *exc) -> None:
        if self._runner:
            await self._runner.cleanup()
