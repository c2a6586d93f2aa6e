// SearchPool: many concurrent alpha-beta searches as cooperative fibers,
// all yielding leaf evaluations into one shared microbatch.
//
// This is the TPU-shaped inversion of the reference's engine tier
// (SURVEY.md §7): instead of N independent engine processes each
// evaluating one position at a time on its own CPU core, N search fibers
// suspend at their leaves; the host collects up to `capacity` pending
// evaluations per step, ships them to the JAX/TPU evaluator in one batch,
// and resumes every fiber with its score.
//
// Driving loop (Python side, engine/tpu_engine.py):
//   submit(...) per position  ->  loop {
//     n = fc_pool_step(feats, buckets, slots)   # run fibers to their leaves
//     if n == 0 and nothing active: break
//     values = jax_evaluate(feats[:n])          # one TPU microbatch
//     fc_pool_provide(values, n)                # wake the fibers
//   }  -> fc_pool_finished() / fc_pool_result_*()
//
// THREADING MODEL: slots are partitioned into n_groups (slot id mod
// n_groups), and each group is owned by exactly one scheduler thread —
// the Python service runs one driver thread per `pipeline_depth` groups
// and any number of such threads. All per-slot and per-group state is
// only ever touched by the owning thread; the cross-thread surfaces are
// the lockless XOR-validated transposition table (search.h), the
// relaxed-atomic counters, the per-slot stop/abort latches, and the
// AIMD speculation-budget state (mutex-guarded, try-lock on the hot
// path). This is the host-parallelism answer to the reference's
// process-per-core model (src/main.rs:158-170): N scheduler threads
// each stepping thousands of fibers, all still sharing one TT so
// adjacent plies of one game share work ACROSS threads.

#include <algorithm>
#include <atomic>
#include <cstdlib>
#include <cstring>
#include <deque>
#include <memory>
#include <mutex>
#include <string>
#include <vector>

#include "fiber.h"
#include "nnue.h"
#include "position.h"
#include "search.h"

namespace fc {

namespace {

int copy_str(const std::string& s, char* buf, int len) {
  if (!buf || len <= 0 || int(s.size()) + 1 > len) return -1;
  memcpy(buf, s.c_str(), s.size() + 1);
  return int(s.size());
}

struct Slot;

// EvalBridge that extracts features and suspends the calling fiber.
// Block requests (prefetched siblings/children) ride one suspension.
class BatchedEval : public EvalBridge {
 public:
  BatchedEval(Slot* slot, const NnueNet* net, const std::atomic<int>* budget,
              const bool* anchors, const bool* placement)
      : slot_(slot),
        net_(net),
        budget_(budget),
        anchors_(anchors),
        placement_(placement) {}
  int evaluate(const Position& pos) override;
  void evaluate_block(const Position* positions, int n, int32_t* out) override;
  bool batched() const override { return true; }
  // Live view of the pool's adaptive speculation budget.
  int prefetch_budget() const override {
    return budget_->load(std::memory_order_relaxed);
  }

 private:
  Slot* slot_;
  const NnueNet* net_;  // PSQT table for the host-side material term
  const std::atomic<int>* budget_;
  // Pool-level persistent-anchor switch (set once by the service before
  // traffic; read-only afterwards).
  const bool* anchors_;
  // Pool-level anchor-placement switch (FISHNET_NO_ANCHOR_PLACEMENT
  // disables the block-reorder policy; read-only after pool creation).
  const bool* placement_;
};

struct Slot {
  std::unique_ptr<Fiber> fiber;
  std::unique_ptr<Search> search;
  std::unique_ptr<BatchedEval> bridge;
  Position root;
  std::vector<uint64_t> history;
  SearchLimits limits;
  SearchResult result;
  // active/finished are written by the owning group's scheduler thread
  // but read cross-thread (fc_pool_active telemetry, submit routing):
  // relaxed atomics. started/wants_eval stay plain bools — owner-thread
  // only.
  std::atomic<bool> active{false};   // submitted, not yet released
  std::atomic<bool> finished{false}; // search complete, result ready
  bool started = false;    // fiber launched
  bool wants_eval = false; // suspended waiting for scores
  bool use_scalar = false; // evaluate immediately with the scalar net
  // Written by fc_pool_stop (driver thread) AND fc_pool_stop_all (any
  // thread, e.g. service close) while the search polls it per node:
  // atomic, relaxed ordering suffices (it's a latch, not a handoff).
  std::atomic<bool> stop_requested{false};
  // Hard abort (no first-iteration guarantee); see SearchLimits.
  std::atomic<bool> abort_requested{false};
  // Eval request state (valid while wants_eval): a block of 1..EVAL_BLOCK_MAX.
  // Features are stored as uint16 (delta indices reach 2*22528+1, still
  // uint16): half the memory per slot and the emission into the device
  // batch is a straight memcpy.
  int block_n = 0;
  uint16_t features[EVAL_BLOCK_MAX][2][NNUE_MAX_ACTIVE];
  int32_t buckets[EVAL_BLOCK_MAX];
  // Per-entry PSQT accumulators, all 8 buckets x both perspectives (stm
  // first), filled host-side during feature extraction: the material
  // term is a ~60-load walk over an L2-resident 720 KB table here,
  // versus a random-gather over an 11 MB padded table on the device —
  // the one NNUE term that is CHEAPER on the scalar side. The wire
  // ships only the bucket-selected material value (4 bytes/entry).
  int32_t psqt[EVAL_BLOCK_MAX][2][NNUE_PSQT_BUCKETS];
  // Bucket-selected material term per entry, ready for the wire.
  int32_t material[EVAL_BLOCK_MAX];
  // Incremental-eval reference, block-relative: -1 = standalone full
  // feature set; >= 0 is (ref_entry << 1) | persp_swap, meaning this
  // entry's features are DELTAS against that (anchor) entry's
  // accumulator, with the two perspectives swapped when the sides to
  // move differ (rebased to batch-relative indices at emission);
  // -2/-3 (PERSISTENT / PERSISTENT_SWAP) mark entry 0 as a delta
  // against the slot's DEVICE-RESIDENT anchor accumulator — the
  // accumulator this slot's previous block stored on the device
  // (emit_block maps these to the wire's table-row codes).
  int32_t parent_code[EVAL_BLOCK_MAX];
  // Device-resident anchor bookkeeping (VERDICT r4 item 1): the
  // position + host-side PSQT accumulators of the accumulator currently
  // stored in this slot's anchor-table row on the device. `pending_*`
  // snapshots entry 0 of the block built most recently — it becomes the
  // slot's anchor when (and only when) that block is actually emitted
  // (a block can wait several steps for batch capacity).
  bool anchor_valid = false;
  bool pending_anchor_valid = false;
  Position anchor_pos;
  Position pending_pos;
  int32_t anchor_psqt[2][NNUE_PSQT_BUCKETS];
  int32_t pending_psqt[2][NNUE_PSQT_BUCKETS];
  int32_t eval_values[EVAL_BLOCK_MAX];
  // Zobrist hash of the position behind each block entry, in fill
  // (wire) order — the key the host-side eval-reuse plane needs to
  // short-circuit or dedup entries before dispatch (ABI 10;
  // fc_pool_batch_hashes exports them batch-ordered).
  uint64_t pos_hash[EVAL_BLOCK_MAX];
};

namespace {

// Full feature extraction for block entry j, including the host-side
// PSQT accumulators (all 8 buckets; the emission picks the entry's own
// bucket and ships one material int32).
void fill_full(Slot* slot, const NnueNet* net, int j, const Position& pos) {
  for (int p = 0; p < 2; p++) {
    uint16_t* row = slot->features[j][p];
    int cnt = nnue_features(pos, p == 0 ? pos.stm : ~pos.stm, row);
    int32_t* ps = slot->psqt[j][p];
    for (int b = 0; b < NNUE_PSQT_BUCKETS; b++) ps[b] = 0;
    for (int i = 0; i < cnt; i++) {
      const int32_t* prow =
          &net->ft_psqt[size_t(row[i]) * NNUE_PSQT_BUCKETS];
      for (int b = 0; b < NNUE_PSQT_BUCKETS; b++) ps[b] += prow[b];
    }
    for (int i = cnt; i < NNUE_MAX_ACTIVE; i++)
      row[i] = uint16_t(NNUE_FEATURES);
  }
  slot->parent_code[j] = -1;
}

// Slot-level parent codes (mapped to the wire encoding at emission).
constexpr int32_t PARENT_FULL = -1;
constexpr int32_t PARENT_PERSISTENT = -2;       // delta vs device anchor row
constexpr int32_t PARENT_PERSISTENT_SWAP = -3;  // ... with perspectives swapped

// Incremental feature extraction: entry j's accumulator = ref's
// accumulator (perspectives swapped if the side to move differs) plus
// the added-piece rows minus the removed-piece rows. Wire contract
// (fishnet_tpu/nnue/spec.py DELTA_SLOTS, ops/ft_gather.py sparse mode):
// per perspective, adds in slots [0, DELTA_SLOTS) padded with the
// sentinel, removals in [DELTA_SLOTS, 2*DELTA_SLOTS) encoded as
// NNUE_DELTA_BASE + index and padded with NNUE_DELTA_BASE + sentinel
// (which decodes back to the zero row); the rest plain sentinel. Only
// valid while each perspective's king is on the same square in both
// positions — a moved king re-bases every feature of that perspective
// (HalfKA king buckets + mirroring), so such entries fall back to a
// full fill. INVARIANT TWIN: cpp/src/nnue.cpp nnue_evaluate_cached
// applies the same rules host-side for the scalar search's incremental
// accumulator — keep the two in lockstep (the parity suites catch
// drift). Typical delta: 1-3 rows per region vs ~30 for a full fill
// — a ~4x cut in row DMAs for the prefetch-block children that
// dominate batch traffic (one move touches at most 2 adds / 3 removes:
// mover or promotion to-piece, plus from-square, victim, e.p. pawn).
// ``ref_psqt`` points at the reference accumulators ([2][8], reference
// perspective order): the anchor entry's in-block psqt, or the slot's
// device-anchor copy. ``ref_entry`` >= 0 encodes an in-block reference;
// -1 encodes a delta against the slot's DEVICE-RESIDENT anchor
// (PARENT_PERSISTENT codes).
bool fill_delta(Slot* slot, const NnueNet* net, int j, const Position& ref,
                const Position& pos,
                const int32_t (*ref_psqt)[NNUE_PSQT_BUCKETS], int ref_entry) {
  constexpr int DELTA_SLOTS = NNUE_DELTA_SLOTS;
  bool swap = pos.stm != ref.stm;
  for (int p = 0; p < 2; p++) {
    Color c = p == 0 ? pos.stm : ~pos.stm;
    if (ref.king_sq(c) != pos.king_sq(c)) return false;
    Square ksq = pos.king_sq(c);
    uint16_t adds[DELTA_SLOTS], rems[DELTA_SLOTS];
    int n_add = 0, n_rem = 0;
    for (int s = 0; s < 64; s++) {
      int before = ref.piece_on(Square(s));
      int after = pos.piece_on(Square(s));
      if (before == after) continue;
      if (before != NO_PIECE) {
        if (n_rem >= DELTA_SLOTS) return false;
        rems[n_rem++] =
            uint16_t(nnue_feature_index(ksq, c, before, Square(s)));
      }
      if (after != NO_PIECE) {
        if (n_add >= DELTA_SLOTS) return false;
        adds[n_add++] = uint16_t(nnue_feature_index(ksq, c, after, Square(s)));
      }
    }
    uint16_t* row = slot->features[j][p];
    for (int i = 0; i < DELTA_SLOTS; i++)
      row[i] = i < n_add ? adds[i] : uint16_t(NNUE_FEATURES);
    for (int i = 0; i < DELTA_SLOTS; i++)
      row[DELTA_SLOTS + i] = uint16_t(
          NNUE_DELTA_BASE + (i < n_rem ? rems[i] : uint16_t(NNUE_FEATURES)));
    for (int i = 2 * DELTA_SLOTS; i < NNUE_MAX_ACTIVE; i++)
      row[i] = uint16_t(NNUE_FEATURES);
    // PSQT: parent's accumulator for the SAME COLOR (parent perspective
    // p^swap), plus the delta rows. Kings match (checked above), so the
    // child's feature indexing agrees with the parent's for this color.
    const int32_t* ref_ps = ref_psqt[swap ? p ^ 1 : p];
    int32_t* ps = slot->psqt[j][p];
    for (int b = 0; b < NNUE_PSQT_BUCKETS; b++) ps[b] = ref_ps[b];
    for (int i = 0; i < n_add; i++) {
      const int32_t* prow = &net->ft_psqt[size_t(adds[i]) * NNUE_PSQT_BUCKETS];
      for (int b = 0; b < NNUE_PSQT_BUCKETS; b++) ps[b] += prow[b];
    }
    for (int i = 0; i < n_rem; i++) {
      const int32_t* prow = &net->ft_psqt[size_t(rems[i]) * NNUE_PSQT_BUCKETS];
      for (int b = 0; b < NNUE_PSQT_BUCKETS; b++) ps[b] -= prow[b];
    }
  }
  slot->parent_code[j] =
      ref_entry >= 0 ? ((ref_entry << 1) | (swap ? 1 : 0))
                     : (swap ? PARENT_PERSISTENT_SWAP : PARENT_PERSISTENT);
  return true;
}

// Exact cheap predictor of fill_delta success: both kings unmoved (a
// moved king re-bases that perspective's whole feature set) and no more
// than NNUE_DELTA_SLOTS added or removed pieces. The piece-diff counts
// are perspective-independent (fill_delta counts ALL board diffs for
// each perspective), so one scan answers for both.
bool can_delta(const Position& ref, const Position& pos) {
  if (ref.king_sq(WHITE) != pos.king_sq(WHITE) ||
      ref.king_sq(BLACK) != pos.king_sq(BLACK))
    return false;
  int n_add = 0, n_rem = 0;
  for (int s = 0; s < 64; s++) {
    int before = ref.piece_on(Square(s));
    int after = pos.piece_on(Square(s));
    if (before == after) continue;
    if (before != NO_PIECE && ++n_rem > NNUE_DELTA_SLOTS) return false;
    if (after != NO_PIECE && ++n_add > NNUE_DELTA_SLOTS) return false;
  }
  return true;
}

// ANCHOR-PLACEMENT POLICY (the wire diet): a deterministic permutation
// of one eval chunk chosen to maximize delta-encodable entries.
//
// The fill loop below encodes entry k as a delta when fill_delta against
// the running anchor succeeds; a failure makes k a full entry AND the
// new anchor. In search emission order one mid-block king-move child
// resets the anchor and cascades fulls over entries that could have
// delta'd against the previous anchor. Since fill_delta requires equal
// king squares for BOTH colors, entries sharing a (white king, black
// king) pair are laid out contiguously — groups ordered by first
// occurrence, original order preserved within a group — so each king
// pair costs at most one full fill instead of one per alternation.
//
// The persistent-anchor candidate: entry 0 may ship as a one-row delta
// against the slot's device-resident anchor, but only entry 0 may carry
// a persistent code. The old code only ever tried positions[0]; here
// the WHOLE chunk is scanned for the first entry delta-encodable
// against the device anchor, and that entry's group leads with it at
// its head — the anchor_coverage lever.
//
// Deterministic: a pure function of (positions, device_anchor), no
// randomness, no iteration-order dependence.
void plan_block_order(const Position* positions, int chunk,
                      const Position* device_anchor, int* order) {
  int group_of[EVAL_BLOCK_MAX];
  int first_of[EVAL_BLOCK_MAX];
  int n_keys = 0;
  for (int j = 0; j < chunk; j++) {
    int g = -1;
    for (int k = 0; k < n_keys; k++) {
      const Position& rep = positions[first_of[k]];
      if (rep.king_sq(WHITE) == positions[j].king_sq(WHITE) &&
          rep.king_sq(BLACK) == positions[j].king_sq(BLACK)) {
        g = k;
        break;
      }
    }
    if (g < 0) {
      g = n_keys++;
      first_of[g] = j;
    }
    group_of[j] = g;
  }
  int j0 = -1;
  if (device_anchor) {
    for (int j = 0; j < chunk; j++)
      if (can_delta(*device_anchor, positions[j])) {
        j0 = j;
        break;
      }
  }
  int lead = j0 >= 0 ? group_of[j0] : 0;  // group 0 starts at entry 0
  int w = 0;
  if (j0 >= 0) order[w++] = j0;
  for (int j = 0; j < chunk; j++)
    if (group_of[j] == lead && j != j0) order[w++] = j;
  for (int g = 0; g < n_keys; g++) {
    if (g == lead) continue;
    for (int j = 0; j < chunk; j++)
      if (group_of[j] == g) order[w++] = j;
  }
}

}  // namespace

void BatchedEval::evaluate_block(const Position* positions, int n, int32_t* out) {
  // Honor the base-class contract for any n: one suspension per chunk of
  // up to EVAL_BLOCK_MAX (search never exceeds one chunk in practice).
  for (int base = 0; base < n; base += EVAL_BLOCK_MAX) {
    int chunk = std::min(n - base, EVAL_BLOCK_MAX);
    // ANCHOR PROTOCOL (the fused TPU kernel depends on it,
    // ops/ft_gather.py): every delta entry references the MOST RECENT
    // anchor entry preceding it — so the kernel reconstructs children
    // from a single running anchor accumulator held in VMEM instead of
    // a batch-wide gather. Entry 0 is always an anchor: full, or (with
    // persistent anchors enabled) a one-row delta against the
    // accumulator this slot's PREVIOUS block stored device-side —
    // single demand evals then ship 32 bytes instead of 128. A failed
    // delta (king moved, too many diffs) becomes full and the new
    // in-block anchor.
    const Position* danchor =
        (*anchors_ && slot_->anchor_valid) ? &slot_->anchor_pos : nullptr;
    // Anchor-placement reorder (plan_block_order): fill the block in a
    // permuted order chosen to maximize delta encodings; `order[k]` is
    // the caller index filled at block entry k, and the result copy-out
    // applies the inverse map. Disabled (identity order) via
    // FISHNET_NO_ANCHOR_PLACEMENT — the pre-policy layout.
    int order[EVAL_BLOCK_MAX];
    if (*placement_ && chunk > 1) {
      plan_block_order(positions + base, chunk, danchor, order);
    } else {
      for (int j = 0; j < chunk; j++) order[j] = j;
    }
    int last_anchor = 0;
    for (int k = 0; k < chunk; k++) {
      const Position& pos = positions[base + order[k]];
      if (k == 0) {
        if (!(danchor && fill_delta(slot_, net_, 0, *danchor, pos,
                                    slot_->anchor_psqt, /*ref_entry=*/-1)))
          fill_full(slot_, net_, 0, pos);
      } else if (!fill_delta(slot_, net_, k,
                             positions[base + order[last_anchor]], pos,
                             slot_->psqt[last_anchor], last_anchor)) {
        fill_full(slot_, net_, k, pos);
        last_anchor = k;
      }
      slot_->buckets[k] = nnue_psqt_bucket(pos);
      slot_->material[k] =
          (slot_->psqt[k][0][slot_->buckets[k]] -
           slot_->psqt[k][1][slot_->buckets[k]]) / 2;
      slot_->pos_hash[k] = pos.hash;
    }
    if (*anchors_) {
      // Block entry 0 becomes the slot's device anchor once this block
      // ships (emit_block finalizes; see the Slot field comment).
      slot_->pending_anchor_valid = true;
      slot_->pending_pos = positions[base + order[0]];
      memcpy(slot_->pending_psqt, slot_->psqt[0], sizeof(slot_->pending_psqt));
    }
    slot_->block_n = chunk;
    slot_->wants_eval = true;
    slot_->fiber->yield();
    slot_->wants_eval = false;
    slot_->block_n = 0;
    // eval_values is in fill (wire) order: undo the permutation.
    for (int k = 0; k < chunk; k++)
      out[base + order[k]] = slot_->eval_values[k];
  }
}

int BatchedEval::evaluate(const Position& pos) {
  int32_t v = 0;
  evaluate_block(&pos, 1, &v);
  return v;
}

}  // namespace

struct SearchPool {
  TranspositionTable tt;
  // Shared continuation-history tables (search.h SharedHistory): like
  // the TT, one instance serves every search and scheduler thread;
  // racy heuristic updates are benign by design.
  SharedHistory shared_history;
  // Pool-level eval-traffic accounting. Written by the scheduler thread
  // only; read cross-thread by fc_pool_counters, hence relaxed atomics.
  SearchCounters counters;
  std::atomic<uint64_t> steps{0};          // device batches shipped
  std::atomic<uint64_t> evals_shipped{0};  // eval slots across all steps
  std::atomic<uint64_t> suspensions{0};    // fiber blocks (1 round-trip each)
  std::atomic<uint64_t> step_capacity{0};  // sum of capacities (occupancy denom)
  std::atomic<uint64_t> delta_evals{0};    // eval slots shipped as deltas
  std::atomic<uint64_t> anchor_evals{0};   // deltas vs device-resident anchors
  // Persistent-anchor switch: set ONCE by the service (before traffic)
  // when its evaluator understands the anchor-table wire codes; plain
  // bool because it is read-only while fibers run.
  bool anchors_enabled = false;
  // Anchor-placement reorder switch (evaluate_block plan_block_order):
  // set once at pool creation from FISHNET_NO_ANCHOR_PLACEMENT,
  // read-only afterwards.
  bool anchor_placement = true;
  // Adaptive speculation budget (max speculative evals per prefetch
  // block). Halved whenever a step overflows capacity — wasted slots
  // then displace other fibers' demand evals — and grown back while
  // batches run at most half full, where an unshipped prefetch would
  // just leave device capacity idle and cost a later round-trip.
  // Written by the scheduler thread, read by it too (via the bridge);
  // atomic only for the telemetry read.
  std::atomic<int> prefetch_budget{EVAL_BLOCK_MAX};
  // fc_pool_set_prefetch pins the budget (parity suites need identical
  // TT evolution across backends; ROI experiments need fixed points).
  // Atomic: written from caller threads while the scheduler reads it.
  std::atomic<bool> prefetch_adaptive{true};
  // ROI window state: speculation must EARN its batch slots. Every
  // ROI_WINDOW non-empty steps the windowed hit rate is checked;
  // unearned budgets halve to 0 and a periodic probe lets a workload
  // whose consumption recovered re-earn it. Measured r2/r3: with a
  // material-blind net the consumption sites (stand-pat windows,
  // delta-pruned captures) almost never fire — ROI 0.0007 — and the
  // wasted slots displaced demand evals 1:1 on a latency-priced link.
  // Guarded by roi_mu: any scheduler thread may run the update after
  // its step (try-lock — a contended update is just skipped), and
  // fc_pool_set_prefetch pins under the same lock, which is what makes
  // a pin un-clobberable by an in-flight AIMD update (the updater
  // re-checks prefetch_adaptive while holding the lock).
  std::mutex roi_mu;
  uint64_t roi_last_shipped = 0;
  uint64_t roi_last_hits = 0;
  uint64_t roi_check_step = 0;
  uint64_t roi_probe_step = 0;
  bool roi_ok = true;  // last window's verdict; gates budget growth
  std::unique_ptr<NnueNet> scalar_net;
  std::unique_ptr<ScalarEval> scalar_eval;
  // Whether the loaded net's eval tracks material (probed once at pool
  // creation): gates the SEE heuristics whose soundness depends on it.
  bool net_material_correlated = false;
  HceEval hce_eval;  // variant searches (immediate, CPU)
  std::vector<std::unique_ptr<Slot>> slots;
  // Slots are partitioned into n_groups (slot id mod n_groups) so the
  // driver can keep several device batches in flight: step/provide act
  // on one group while other groups' evals ride the wire. Each group
  // keeps its own emission record and fairness cursor.
  int n_groups = 1;
  // (slot id, index within the slot's block) per entry of the group's
  // last step() eval batch, in emission order.
  std::vector<std::vector<std::pair<int, int>>> group_batch;
  // Finished-slot queues, one per group: filled by the owning thread's
  // step(), drained by the same thread's harvest loop.
  std::vector<std::deque<int>> group_finished;
  // Round-robin scan origin per group: each step starts scanning just
  // past the last slot served, so over-capacity steps rotate service
  // instead of starving high-index slots (head-of-line fairness).
  std::vector<size_t> group_cursor;
  // Worst case per fiber.h's sizing analysis (MAX_PLY frames + qsearch
  // tail at ~2.5 KB/frame): needs the full 512 KB; pages commit lazily.
  size_t fiber_stack = 512 * 1024;

  SearchPool(int max_slots, size_t tt_bytes, int groups) : tt(tt_bytes) {
    slots.resize(max_slots);
    for (auto& s : slots) s = std::make_unique<Slot>();
    n_groups = groups < 1 ? 1 : (groups > max_slots ? max_slots : groups);
    group_batch.resize(n_groups);
    group_finished.resize(n_groups);
    group_cursor.assign(n_groups, 0);
  }
};

extern "C" {

SearchPool* fc_pool_new(int max_slots, uint64_t tt_bytes,
                        const char* scalar_net_path, int n_groups) {
  init_bitboards();
  init_zobrist();
  auto* pool = new (std::nothrow) SearchPool(
      max_slots > 0 ? max_slots : 256,
      tt_bytes ? size_t(tt_bytes) : (64ull << 20), n_groups);
  if (!pool) return nullptr;
  // Escape hatch for the block-reorder anchor-placement policy
  // (evaluate_block): restores the pre-policy search-emission layout.
  const char* no_placement = std::getenv("FISHNET_NO_ANCHOR_PLACEMENT");
  pool->anchor_placement = !(no_placement && no_placement[0] == '1');
  if (scalar_net_path && scalar_net_path[0]) {
    pool->scalar_net = std::make_unique<NnueNet>();
    if (!pool->scalar_net->load(scalar_net_path).empty()) {
      delete pool;
      return nullptr;
    }
    pool->scalar_eval = std::make_unique<ScalarEval>(pool->scalar_net.get());
    pool->net_material_correlated =
        nnue_material_correlated(*pool->scalar_net);
  }
  return pool;
}

void fc_pool_free(SearchPool* pool) { delete pool; }

// Submit a search into `group`'s slot partition (the caller must be, or
// coordinate with, that group's owning thread; pass -1 for any group —
// only safe while a single thread drives the whole pool). moves:
// space-separated UCI from the root fen (the game line, for
// history/repetitions). variant: a VariantRules value; non-standard
// variants are evaluated with the classical HCE on the host (the
// reference's MultiVariant flavor) and never suspend for the device.
// Returns the slot id, or a negative error: -1 group/pool full (retry
// after a release), -2/-3 invalid fen/variant/moves, -4 fiber stack
// exhaustion, -5 standard-variant search on a pool built without a
// scalar net (a configuration error — resubmitting cannot clear it).
// skill: engine strength −9..20; <20 enables the weakened best-move
// sampling in Search::run (play jobs; analysis always passes 20).
int fc_pool_submit(SearchPool* pool, int group, const char* fen,
                   const char* moves, uint64_t nodes, int depth, int multipv,
                   int skill, int use_scalar, int variant) {
  if (group >= pool->n_groups) return -1;
  int id = -1;
  for (size_t i = group < 0 ? 0 : size_t(group); i < pool->slots.size();
       i += group < 0 ? 1 : size_t(pool->n_groups))
    if (!pool->slots[i]->active) {
      id = int(i);
      break;
    }
  if (id < 0) return -1;
  Slot& slot = *pool->slots[id];

  if (variant < VR_STANDARD || variant > VR_THREE_CHECK) return -2;
  // A standard-variant search needs the scalar net: the batched bridge
  // walks net->ft_psqt host-side (fill_full/fill_delta material term)
  // and the scalar backend IS the net — and a use_scalar request with
  // no net would silently fall back to that same bridge. Refuse the
  // submit instead of crashing later; a netless pool (fc_pool_new
  // allows one) still serves variant/HCE searches.
  if (variant == VR_STANDARD && !pool->scalar_net) return -5;
  Position pos;
  if (!pos.set_fen(fen ? fen : "", VariantRules(variant)).empty()) return -2;
  slot.history.clear();
  slot.history.push_back(pos.hash);
  if (moves && moves[0]) {
    std::string all(moves);
    size_t start = 0;
    while (start < all.size()) {
      size_t end = all.find(' ', start);
      if (end == std::string::npos) end = all.size();
      std::string uci = all.substr(start, end - start);
      start = end + 1;
      if (uci.empty()) continue;
      Move m = pos.parse_uci(uci);
      if (m == MOVE_NONE) return -3;
      pos.make(m);
      slot.history.push_back(pos.hash);
    }
  }

  slot.root = pos;
  slot.limits.nodes = nodes;
  slot.limits.depth = depth;
  slot.limits.multipv = multipv;
  slot.limits.skill = std::max(-9, std::min(20, skill));
  slot.stop_requested = false;
  slot.abort_requested = false;
  slot.limits.stop = &slot.stop_requested;
  slot.limits.abort_now = &slot.abort_requested;
  slot.use_scalar = use_scalar != 0 && pool->scalar_eval != nullptr;
  slot.active = true;
  slot.started = false;
  slot.finished = false;
  slot.wants_eval = false;
  slot.result = SearchResult();
  if (!slot.fiber) slot.fiber = std::make_unique<Fiber>(pool->fiber_stack);
  if (!slot.fiber->valid()) {
    // Stack mmap failed (memory pressure / map-count exhaustion): refuse
    // the slot instead of crashing in makecontext later.
    slot.fiber.reset();
    slot.active = false;
    return -4;
  }
  // A fresh search must not diff against a previous occupant's anchor.
  slot.anchor_valid = false;
  slot.pending_anchor_valid = false;
  if (!slot.bridge)
    slot.bridge = std::make_unique<BatchedEval>(
        &slot, pool->scalar_net.get(), &pool->prefetch_budget,
        &pool->anchors_enabled, &pool->anchor_placement);
  return id;
}

// Enable persistent device-resident anchors: entry 0 of every eval
// block may ship as a one-row delta against the accumulator the slot's
// previous block stored in its anchor-table row (wire parent codes
// <= -2; see emit_block). Only call when the evaluator implements the
// anchor table (jax_eval.evaluate_packed_anchored) and BEFORE any
// submissions. With anchors on, every step's batch must be provided IN
// FULL (fc_pool_provide n == the step's return): a partial provide
// re-emits a block whose entry-0 delta references an anchor row the
// first emission already overwrote. The one caller (search service)
// always provides in full.
void fc_pool_set_anchors(SearchPool* pool, int enable) {
  pool->anchors_enabled = enable != 0;
}

// Pin (adaptive=0) or re-seed (adaptive=1) the speculation budget.
// Pinned budgets make TT evolution a deterministic function of the
// submission sequence — required by the cross-backend parity suites —
// and give ROI experiments fixed operating points.
void fc_pool_set_prefetch(SearchPool* pool, int budget, int adaptive) {
  if (budget < 0) budget = 0;
  if (budget > EVAL_BLOCK_MAX) budget = EVAL_BLOCK_MAX;
  // Under roi_mu: an in-flight AIMD update (which holds the lock and
  // re-checks prefetch_adaptive inside it) can neither clobber the pin
  // nor interleave half of one.
  std::lock_guard<std::mutex> lk(pool->roi_mu);
  pool->prefetch_adaptive.store(adaptive != 0, std::memory_order_relaxed);
  pool->prefetch_budget.store(budget, std::memory_order_relaxed);
}

void fc_pool_stop(SearchPool* pool, int slot_id) {
  if (slot_id >= 0 && slot_id < int(pool->slots.size()))
    pool->slots[slot_id]->stop_requested = true;
}

// Stop every active search. Unlike fc_pool_stop (driver-thread only,
// slot-id addressed), this is safe to call from ANY thread while the
// driver is blocked inside fc_pool_step: each search polls its
// stop_requested flag per node, so a long-running scalar search unwinds
// promptly. Used by service shutdown.
// Mass stops/aborts invalidate the speculation-ROI window: the drain
// ships prefetches for fibers that are about to die and can never
// consume them, so the next verdict would judge the POLICY on teardown
// traffic and zero the budget for minutes into the following load
// (measured: a post-drain window ran at budget 0 start to finish).
// Restart the window at the current counters and forgive the verdict.
static void reset_roi_window(SearchPool* pool) {
  std::lock_guard<std::mutex> lk(pool->roi_mu);
  pool->roi_last_shipped =
      pool->counters.prefetch_shipped.load(std::memory_order_relaxed);
  pool->roi_last_hits =
      pool->counters.prefetch_hits.load(std::memory_order_relaxed);
  pool->roi_check_step = pool->steps.load(std::memory_order_relaxed);
  pool->roi_ok = true;
}

void fc_pool_stop_all(SearchPool* pool) {
  for (auto& slot : pool->slots) slot->stop_requested = true;
  reset_roi_window(pool);
}

// Hard-abort every active search: unwind at the next node without the
// first-iteration guarantee (results may be empty). For teardown paths
// where wall clock matters more than partial results — on a ~150 ms
// round-trip link a graceful drain of thousands of young fibers costs
// minutes; this costs one step. Safe from any thread.
void fc_pool_abort_all(SearchPool* pool) {
  for (auto& slot : pool->slots) slot->abort_requested = true;
  reset_roi_window(pool);
}

// Run all runnable fibers until each is blocked on an eval or finished.
// Writes up to `capacity` pending eval requests (features [i][2][32],
// bucket [i], slot id [i]) and returns the count. Returns 0 when no
// fiber is waiting for evals (check fc_pool_finished for results).
namespace {

// Append slot i's whole eval block to the group's outgoing batch if it
// fits. COMPACT WIRE FORMAT (VERDICT r3 item 4): features go out as a
// packed stream of uint16 [2][8] rows plus one int32 row-offset per
// entry — a full entry owns 4 consecutive rows (its 32 slots per
// perspective, 8 at a time), an incremental (delta) entry owns ONE row
// (its 2*NNUE_DELTA_SLOTS live slots; the other 24 are sentinel by
// contract and are reconstructed device-side). Deltas ship 32 bytes
// instead of 128 — the wire cost that made speculation net-negative on
// payload-priced links is quartered exactly where speculation grows
// the batch.
// Result of trying to place one slot's eval block into the batch.
enum EmitResult {
  EMIT_OK = 0,        // emitted
  EMIT_FULL = 1,      // batch out of capacity: genuine pressure signal
  EMIT_MISALIGNED = 2 // block would straddle a shard boundary; NOT
                      // pressure — the AIMD budget must not react, or
                      // routine straddles would pin speculation at 0
};

EmitResult emit_block(SearchPool* pool,
                      std::vector<std::pair<int, int>>& batch,
                      int i, uint16_t* out_packed, int32_t* out_offsets,
                      int32_t* out_buckets,
                      int32_t* out_slots, int32_t* out_parent,
                      int32_t* out_material, int capacity, int align,
                      int& row_cursor) {
  Slot& slot = *pool->slots[i];
  int base = int(batch.size());
  // (In-step dedup used to alias identical single requests here; it was
  // DELETED per VERDICT r4 item 8 — measured 0.05-0.3% of evals on
  // production-shaped adjacent-ply workloads, while its hash-map build
  // sat on the hot per-step host path. The TT already dedups across
  // steps: the first eval lands there at provide time.)
  if (base + slot.block_n > capacity) return EMIT_FULL;  // next step
  // Shard alignment (sharded serving): a block must not straddle an
  // `align`-entry boundary, so every delta entry and its anchor land in
  // the same mesh shard and the sharded eval needs NO cross-device
  // gather (parallel/mesh.py ShardedEvaluator runs shard_map with
  // shard-local parent codes). Smaller blocks from other fibers can
  // still fill the gap this block skipped.
  if (align > 0 && slot.block_n > 1 &&
      base / align != (base + slot.block_n - 1) / align)
    return EMIT_MISALIGNED;
  // One fiber block served by this device round-trip.
  pool->suspensions.fetch_add(1, std::memory_order_relaxed);
  constexpr int ROW = 8;                        // slots per packed row
  constexpr int FULL_ROWS = NNUE_MAX_ACTIVE / ROW;
  for (int j = 0; j < slot.block_n; j++) {
    int idx = base + j;
    int32_t code = slot.parent_code[j];
    out_offsets[idx] = row_cursor;
    // Persistent-delta entries (code <= PARENT_PERSISTENT) ship one
    // row exactly like in-block deltas.
    if (code >= 0 || code <= PARENT_PERSISTENT) {
      // Delta entry: one packed row carries its 2*NNUE_DELTA_SLOTS live
      // slots per perspective (= ROW with the spec's DELTA_SLOTS of 4).
      for (int p = 0; p < 2; p++)
        memcpy(out_packed + (size_t(row_cursor) * 2 + p) * ROW,
               &slot.features[j][p][0], sizeof(uint16_t) * ROW);
      row_cursor += 1;
    } else {
      for (int r = 0; r < FULL_ROWS; r++)
        for (int p = 0; p < 2; p++)
          memcpy(out_packed + (size_t(row_cursor + r) * 2 + p) * ROW,
                 &slot.features[j][p][r * ROW], sizeof(uint16_t) * ROW);
      row_cursor += FULL_ROWS;
    }
    out_buckets[idx] = slot.buckets[j];
    out_slots[idx] = i;
    // ABI 9: the material column is optional — callers running the
    // device-resident PSQT path (fused kernel / XLA twin plus the
    // anchor-PSQT table) pass nullptr and the wire drops 4 bytes/entry.
    // The host-side walk still runs (slot.material feeds the stale-
    // batch repair and the CPU/XLA fallback wire).
    if (out_material) out_material[idx] = slot.material[j];
    // WIRE parent encoding: -1 plain full; >= 0 in-batch delta
    // (ref << 1 | swap, rebased from block entries to batch positions —
    // the whole block ships in this batch, so the reference resolves
    // within the same device call; blocks are emitted contiguously, so
    // the anchor protocol's "most recent preceding anchor entry"
    // invariant carries over to batch indices unchanged); <= -2 anchor-
    // entry codes: -(2 + v) with v = (table_row << 2) | (is_delta << 1)
    // | swap — the entry resolves against (is_delta) or refreshes
    // (always) the slot's device-resident anchor-table row.
    if (code >= 0) {
      out_parent[idx] = int32_t(((base + (code >> 1)) << 1) | (code & 1));
      pool->delta_evals.fetch_add(1, std::memory_order_relaxed);
    } else if (j == 0 && slot.pending_anchor_valid) {
      int32_t aid = i / pool->n_groups;  // slot's row in its group's table
      int32_t v;
      if (code <= PARENT_PERSISTENT) {
        v = (aid << 2) | 2 | (code == PARENT_PERSISTENT_SWAP ? 1 : 0);
        pool->delta_evals.fetch_add(1, std::memory_order_relaxed);
        pool->anchor_evals.fetch_add(1, std::memory_order_relaxed);
      } else {
        v = aid << 2;  // full entry that (re)seeds the anchor row
      }
      out_parent[idx] = -(2 + v);
    } else {
      out_parent[idx] = -1;
    }
    batch.emplace_back(i, j);
  }
  // The block is on the wire: entry 0's accumulator is (about to be)
  // the slot's device-side anchor.
  if (slot.pending_anchor_valid) {
    slot.anchor_pos = slot.pending_pos;
    memcpy(slot.anchor_psqt, slot.pending_psqt, sizeof(slot.anchor_psqt));
    slot.anchor_valid = true;
    slot.pending_anchor_valid = false;
  }
  return EMIT_OK;
}

}  // namespace

// `align` > 0 keeps every emitted block inside one align-entry span of
// the batch (sharded serving passes the mesh shard size; 0 disables).
// Callers must keep align >= EVAL_BLOCK_MAX or a maximal block could
// never be placed.
//
// out_packed must hold 4*capacity rows of uint16[2][8] (worst case:
// all entries full); out_offsets/out_buckets/out_slots/out_parent/
// out_material hold `capacity` int32 each. out_material may be nullptr
// (ABI 9): the material column is then skipped — for evaluators that
// resolve PSQT entirely on device (fused kernel + anchor-PSQT table).
// *out_rows receives the number of packed rows written.
int fc_pool_step(SearchPool* pool, int group, uint16_t* out_packed,
                 int32_t* out_offsets, int32_t* out_buckets,
                 int32_t* out_slots, int32_t* out_parent,
                 int32_t* out_material, int capacity, int align,
                 int32_t* out_rows) {
  if (group < 0 || group >= pool->n_groups) group = 0;
  auto& batch = pool->group_batch[group];
  // Defensive repair for the step-without-provide contract breach: a
  // stale batch here means the previous step's values never arrived, so
  // its blocks re-emit below (phase 1). With anchors enabled, an
  // entry-0 persistent delta would then resolve against the anchor row
  // its FIRST emission already refreshed — i.e. against itself. Rebuild
  // such entries as full fills (anchor_pos holds entry 0's own
  // position, committed at emission) and invalidate the slot's device
  // anchor so later blocks reseed instead of diffing against a row
  // whose content is now unknown.
  if (pool->anchors_enabled && !batch.empty() && pool->scalar_net) {
    for (auto [sid, bidx] : batch) {
      if (bidx != 0) continue;
      Slot& slot = *pool->slots[sid];
      if (!slot.wants_eval) continue;
      if (slot.parent_code[0] <= PARENT_PERSISTENT) {
        fill_full(&slot, pool->scalar_net.get(), 0, slot.anchor_pos);
        slot.material[0] =
            (slot.psqt[0][0][slot.buckets[0]] -
             slot.psqt[0][1][slot.buckets[0]]) / 2;
      }
      slot.anchor_valid = false;
      slot.pending_anchor_valid = false;
    }
  }
  batch.clear();
  const size_t n_slots = pool->slots.size();
  const int n_groups = pool->n_groups;
  size_t cursor = pool->group_cursor[group];
  bool overflow = false;
  int row_cursor = 0;

  // Phase 1: fibers still suspended from a previous over-capacity step
  // have waited longest — serve them before any freshly-produced blocks
  // can refill the batch.
  for (size_t k = 0; k < n_slots; k++) {
    size_t i = (cursor + k) % n_slots;
    if (int(i) % n_groups != group) continue;
    Slot& slot = *pool->slots[i];
    if (!slot.active || slot.finished || !slot.wants_eval) continue;
    if (emit_block(pool, batch, int(i), out_packed,
                   out_offsets, out_buckets, out_slots, out_parent,
                   out_material, capacity, align, row_cursor) == EMIT_FULL)
      overflow = true;
  }

  // Phase 2: run every runnable fiber to its next leaf; emit the blocks
  // they produce as long as they fit. (Slots emitted in phase 1 still
  // have wants_eval set and are skipped here.)
  for (size_t k = 0; k < n_slots; k++) {
    size_t i = (cursor + k) % n_slots;
    if (int(i) % n_groups != group) continue;
    Slot& slot = *pool->slots[i];
    if (!slot.active || slot.finished || slot.wants_eval) continue;

    if (!slot.started) {
      if (int(batch.size()) >= capacity) continue;  // defer launch
      slot.started = true;
      Slot* sp = &slot;
      SearchPool* pp = pool;
      EvalBridge* eval =
          slot.root.variant != VR_STANDARD
              ? static_cast<EvalBridge*>(&pp->hce_eval)
          : slot.use_scalar
              ? static_cast<EvalBridge*>(pp->scalar_eval.get())
              : static_cast<EvalBridge*>(slot.bridge.get());
      // HCE is material by construction; NNUE searches get the full SEE
      // policy only when the loaded net's eval was probed to track
      // material (random test nets must not be pruned by material logic).
      bool see_full = slot.root.variant != VR_STANDARD
                          ? true
                          : pp->net_material_correlated;
      slot.search = std::make_unique<Search>(
          &pp->tt, eval, &pp->counters, see_full, &pp->shared_history);
      slot.fiber->start([sp] {
        sp->result = sp->search->run(sp->root, sp->history, sp->limits);
      });
    } else {
      slot.fiber->resume();
    }

    if (slot.fiber->done()) {
      slot.finished = true;
      pool->group_finished[group].push_back(int(i));
    } else if (slot.wants_eval) {
      // Blocks that don't fit stay suspended; phase 1 of the next step
      // picks them up first.
      if (emit_block(pool, batch, int(i), out_packed,
                     out_offsets, out_buckets, out_slots, out_parent,
                     out_material, capacity, align, row_cursor) == EMIT_FULL)
        overflow = true;
    }
  }

  // Rotate: next step starts scanning just past the last slot served.
  if (!batch.empty())
    pool->group_cursor[group] = (size_t(batch.back().first) + 1) % n_slots;

  if (!batch.empty()) {
    // Only non-empty steps ship a device batch; idle polls don't count
    // against occupancy.
    pool->steps.fetch_add(1, std::memory_order_relaxed);
    pool->step_capacity.fetch_add(uint64_t(capacity), std::memory_order_relaxed);
    pool->evals_shipped.fetch_add(batch.size(), std::memory_order_relaxed);
    // Adapt the speculation budget to batch pressure (see the field's
    // comment): multiplicative decrease on overflow, slow additive
    // growth while there is slack. The floor is 0, not 1: when
    // speculation is not earning (VERDICT r2: ROI 0.0008 before the
    // store_eval fix), the policy must be able to turn it off outright.
    if (pool->prefetch_adaptive.load(std::memory_order_relaxed)) {
      // Try-lock: budget adaptation is advisory — if another scheduler
      // thread is mid-update, skip this step's contribution. The
      // re-check of prefetch_adaptive UNDER the lock is what makes a
      // concurrent fc_pool_set_prefetch pin un-clobberable (the pin
      // writer holds the same lock; VERDICT r3 ADVICE: the old CAS let
      // a same-value pin be overwritten by an AIMD result).
      // ROI gate, judged on a step window: speculative slots that are
      // not being consumed (hits/shipped below threshold) displace
      // other fibers' demand evals for nothing — the verdict gates
      // growth and decays the budget all the way to 0. A zero budget
      // ships no speculation, so ROI could never recover by itself:
      // probe with a tiny budget every ROI_PROBE steps and let the next
      // window's verdict re-zero or re-grow it. Measured r2/r3: with a
      // material-blind net the consumption sites (stand-pat alpha
      // windows, delta-pruned captures) almost never fire — ROI 0.0007
      // while ~45% of shipped slots were speculative waste.
      std::unique_lock<std::mutex> lk(pool->roi_mu, std::try_to_lock);
      if (lk.owns_lock() &&
          pool->prefetch_adaptive.load(std::memory_order_relaxed)) {
        // ROI_PROBE at 512 steps was ~4 minutes of wall clock at the
        // slow link's ~2 steps/s — a zeroed budget could not recover
        // within a bench window. 128 keeps probe overhead negligible
        // (2 slots per 128 steps) while bounding budget-0 stretches to
        // ~1 minute.
        constexpr uint64_t ROI_WINDOW = 32, ROI_PROBE = 128;
        constexpr uint64_t ROI_MIN_SAMPLE = 2048;
        uint64_t step_now = pool->steps.load(std::memory_order_relaxed);
        if (step_now - pool->roi_check_step >= ROI_WINDOW) {
          uint64_t shipped =
              pool->counters.prefetch_shipped.load(std::memory_order_relaxed);
          uint64_t hits =
              pool->counters.prefetch_hits.load(std::memory_order_relaxed);
          uint64_t sd = shipped - pool->roi_last_shipped;
          if (sd >= ROI_MIN_SAMPLE) {
            pool->roi_ok =
                double(hits - pool->roi_last_hits) >= 0.05 * double(sd);
            pool->roi_last_shipped = shipped;
            pool->roi_last_hits = hits;
            pool->roi_check_step = step_now;
          }
        }
        int budget = pool->prefetch_budget.load(std::memory_order_relaxed);
        int next = budget;
        if (!pool->roi_ok) {
          // Not earning: collapse fast (the periodic probe re-earns).
          next = budget / 2;
        } else if (overflow) {
          // Capacity pressure with GOOD ROI: back off gently — the
          // compact wire prices speculative delta slots at a quarter of
          // a full entry, so the equilibrium should sit near capacity
          // rather than sawtooth far below it (measured r4: /2 decay
          // pinned the budget at 5-7 against a 40-slot ceiling).
          next = std::max(0, budget - 1 - budget / 8);
        } else if (int(batch.size()) + EVAL_BLOCK_MAX <= capacity &&
                   budget < EVAL_BLOCK_MAX) {
          // Growth keys on BUCKET HEADROOM (another maximal block would
          // have fit this step) + the ROI verdict above — NOT on the
          // batch running under half capacity, which never held at the
          // 0.80-occupancy equilibrium the e2e workload settles into
          // (VERDICT r3 weak #3: ROI 0.41 yet the budget sat at 1,
          // starving speculation of ~3.3k free slots per 16k bucket;
          // "earns but isn't allowed to spend").
          next = budget + 1;
        }
        if (budget == 0 && next == 0 &&
            step_now - pool->roi_probe_step >= ROI_PROBE) {
          next = 2;
          pool->roi_ok = true;  // let the probe ship and be judged
          pool->roi_probe_step = step_now;
          // Restart the window so the probe's own shipments are judged.
          pool->roi_last_shipped =
              pool->counters.prefetch_shipped.load(std::memory_order_relaxed);
          pool->roi_last_hits =
              pool->counters.prefetch_hits.load(std::memory_order_relaxed);
          pool->roi_check_step = step_now;
        }
        if (next != budget)
          pool->prefetch_budget.store(next, std::memory_order_relaxed);
      }
    }
  }
  if (out_rows) *out_rows = row_cursor;
  return int(batch.size());
}

// Cumulative eval-traffic counters, for bench/telemetry:
// [0] steps (device batches shipped)   [1] eval slots shipped
// [2] fiber suspensions served         [3] sum of step capacities
// [4] demand evals                     [5] prefetched (speculative) evals
// [6] prefetch hits                    [7] TT static-eval hits
// [8] current prefetch budget (adaptive; instantaneous, not cumulative)
// [9] eval slots shipped as incremental deltas (DMA-savings coverage)
// [10] RETIRED (was in-step dedup; always 0 — the alias machinery was
//      deleted after measuring 0.05-0.3% on adjacent-ply workloads)
// [11] search nodes visited, LIVE (bumped per node, not at finish) —
//      lets telemetry compute steady-state nps over a time window
//      without waiting for searches to complete
// [12] eval slots shipped as deltas against DEVICE-RESIDENT anchors
//      (subset of [9]; the persistent-anchor coverage metric)
int fc_pool_counters(SearchPool* pool, uint64_t* out, int n) {
  constexpr auto R = std::memory_order_relaxed;
  const uint64_t vals[13] = {
      pool->steps.load(R),          pool->evals_shipped.load(R),
      pool->suspensions.load(R),    pool->step_capacity.load(R),
      pool->counters.demand_evals.load(R),
      pool->counters.prefetch_shipped.load(R),
      pool->counters.prefetch_hits.load(R),
      pool->counters.tt_eval_hits.load(R),
      uint64_t(pool->prefetch_budget.load(R)),
      pool->delta_evals.load(R),
      0,  // retired dedup slot
      pool->counters.nodes.load(R),
      pool->anchor_evals.load(R),
  };
  int k = n < 13 ? n : 13;
  for (int i = 0; i < k; i++) out[i] = vals[i];
  return k;
}

// Provide centipawn scores for the group's last step() batch, in order.
// A fiber resumes (on the group's next fc_pool_step) once its whole
// block has values; the service always provides all n requested.
//
// Returns the number of entries consumed, or -1 on a FULL-PROVIDE
// contract violation: with persistent anchors enabled (fc_pool_set_
// anchors), a provide with n != the step's batch size is REFUSED and
// consumes nothing — a partial provide would re-emit blocks whose
// entry-0 persistent delta references an anchor-table row the first
// emission already overwrote, silently corrupting device anchor state
// (ADVICE r5 #1). The caller may retry with the full batch; the batch
// mapping is left intact. Without anchors the legacy lenient behavior
// is kept (clamp to the batch, consume, clear).
int fc_pool_provide(SearchPool* pool, int group, const int32_t* values, int n) {
  if (group < 0 || group >= pool->n_groups) group = 0;
  auto& batch = pool->group_batch[group];
  if (pool->anchors_enabled && n != int(batch.size())) return -1;
  int consumed = n < int(batch.size()) ? n : int(batch.size());
  for (int i = 0; i < consumed; i++) {
    auto [sid, bidx] = batch[i];
    Slot& slot = *pool->slots[sid];
    slot.eval_values[bidx] = values[i];
    if (bidx == slot.block_n - 1) slot.wants_eval = false;  // runnable again
  }
  batch.clear();
  return consumed;
}

// Number of slots still working (active and not finished) in `group`,
// or pool-wide with group < 0. Cross-thread safe (relaxed-atomic slot
// flags); the count is a momentary snapshot.
int fc_pool_active(SearchPool* pool, int group) {
  int n = 0;
  for (size_t i = 0; i < pool->slots.size(); i++) {
    if (group >= 0 && int(i) % pool->n_groups != group) continue;
    Slot& s = *pool->slots[i];
    if (s.active && !s.finished) n++;
  }
  return n;
}

// Drain one finished slot id from `group`'s queue, or -1. Owner-thread
// only (like step/provide for the same group).
int fc_pool_next_finished(SearchPool* pool, int group) {
  if (group < 0 || group >= pool->n_groups) group = 0;
  auto& q = pool->group_finished[group];
  if (q.empty()) return -1;
  int id = q.front();
  q.pop_front();
  return id;
}

int fc_pool_result_summary(SearchPool* pool, int slot_id, uint64_t* nodes,
                           int32_t* depth, char* bestmove, int bmlen,
                           int32_t* nlines) {
  if (slot_id < 0 || slot_id >= int(pool->slots.size())) return -1;
  Slot& slot = *pool->slots[slot_id];
  if (!slot.finished) return -1;
  *nodes = slot.result.nodes;
  *depth = slot.result.depth;
  *nlines = int32_t(slot.result.lines.size());
  std::string bm = slot.result.best_move == MOVE_NONE
                       ? ""
                       : slot.root.uci(slot.result.best_move);
  return copy_str(bm, bestmove, bmlen);
}

int fc_pool_result_line(SearchPool* pool, int slot_id, int line_idx,
                        int32_t* multipv, int32_t* depth, int32_t* is_mate,
                        int32_t* value, char* pv, int pvlen) {
  if (slot_id < 0 || slot_id >= int(pool->slots.size())) return -1;
  Slot& slot = *pool->slots[slot_id];
  if (!slot.finished || line_idx < 0 || line_idx >= int(slot.result.lines.size()))
    return -1;
  const PvLine& line = slot.result.lines[line_idx];
  *multipv = line.multipv;
  *depth = line.depth;
  *is_mate = line.mate ? 1 : 0;
  *value = line.value;
  // Render the PV by replaying from the root (castling notation etc.).
  std::string out;
  Position pos = slot.root;
  for (Move m : line.pv) {
    if (!out.empty()) out += ' ';
    out += pos.uci(m);
    pos.make(m);
  }
  return copy_str(out, pv, pvlen);
}

// Export the Zobrist hashes of `group`'s current pending batch, batch
// order (ABI 10). Owner-thread only (same discipline as step/provide).
// Writes min(batch, cap) hashes into `out`, returns the batch size so
// a too-small buffer is detectable.
int fc_pool_batch_hashes(SearchPool* pool, int group, uint64_t* out, int cap) {
  if (group < 0 || group >= pool->n_groups) group = 0;
  auto& batch = pool->group_batch[group];
  int n = int(batch.size()) < cap ? int(batch.size()) : cap;
  for (int i = 0; i < n; i++) {
    auto [sid, bidx] = batch[i];
    out[i] = pool->slots[sid]->pos_hash[bidx];
  }
  return int(batch.size());
}

// Invalidate the device-resident anchors of every slot whose block sits
// in `group`'s pending batch (ABI 10). Required before providing values
// for a batch the caller decided NOT to ship to the device: emit_block
// already committed entry 0 as the slot's anchor, but the device
// anchor-table row was never (re)written, so later blocks must reseed
// with a full entry instead of delta-ing against a stale row. Owner-
// thread only. Returns the number of slots invalidated.
int fc_pool_cancel_anchors(SearchPool* pool, int group) {
  if (group < 0 || group >= pool->n_groups) group = 0;
  int n = 0;
  for (auto& [sid, bidx] : pool->group_batch[group]) {
    if (bidx != 0) continue;
    Slot& slot = *pool->slots[sid];
    if (slot.anchor_valid) n++;
    slot.anchor_valid = false;
    slot.pending_anchor_valid = false;
  }
  return n;
}

// Provide-time TT fill (ABI 10): land an externally-known static eval
// (e.g. the process-wide Python EvalCache) in the pool's own TT so the
// next search touching `key` takes the tt_eval_hits fast path and never
// requests the eval at all. The lockless xor-validated TT is safe to
// call from any thread; store_eval never evicts entries carrying
// bounds/evals for other keys.
void fc_pool_tt_fill(SearchPool* pool, uint64_t key, int32_t eval) {
  pool->tt.store_eval(key, int(eval));
}

// Bound-record TT fill (ABI 11): land a full search fact — value (in
// stored/value_to_tt form), static eval, depth, bound type and best
// move — in the pool's TT so the next search touching `key` gets a
// cutoff or move-ordering hint, not just a cheap eval. `move_bits` is
// the 21-bit packed move (0x1FFFFF = none); a move from a foreign
// position is safe — search only ever COMPARES tt moves against
// generated legal moves, never plays them blindly. Lockless
// xor-validated TT: any-thread safe.
void fc_pool_tt_fill_bound(SearchPool* pool, uint64_t key, int32_t value,
                           int32_t eval, int32_t depth, int32_t bound,
                           uint32_t move_bits) {
  if (bound <= TT_NONE || bound > TT_EXACT) return;
  Move m = move_bits >= 0x1FFFFF ? MOVE_NONE : Move(move_bits);
  pool->tt.store(key, m, int(value), int(eval), int(depth), TTBound(bound));
}

// Bound-record TT export (ABI 11): probe `n` keys against the pool's
// TT and write out the bound-carrying entries so the host can promote
// the pool's private search facts into the process/fleet bounds tier.
// Rows that miss (or carry no bound) get out_bounds[i] = 0 and the
// other columns untouched. Values are exported in stored
// (value_to_tt) form and round-trip verbatim through
// fc_pool_tt_fill_bound. Returns the hit count. Lockless TT:
// any-thread safe.
int fc_pool_tt_export(SearchPool* pool, const uint64_t* keys, int n,
                      int32_t* out_values, int32_t* out_evals,
                      int32_t* out_depths, int32_t* out_bounds,
                      uint32_t* out_moves) {
  int hits = 0;
  for (int i = 0; i < n; i++) {
    out_bounds[i] = 0;
    TTData tte;
    if (!pool->tt.probe(keys[i], tte)) continue;
    if (tte.bound == TT_NONE) continue;
    out_values[i] = tte.value;
    out_evals[i] = tte.eval;
    out_depths[i] = tte.depth;
    out_bounds[i] = int32_t(tte.bound);
    out_moves[i] =
        tte.move == MOVE_NONE ? 0x1FFFFF : uint32_t(tte.move) & 0x1FFFFF;
    hits++;
  }
  return hits;
}

void fc_pool_release(SearchPool* pool, int slot_id) {
  if (slot_id >= 0 && slot_id < int(pool->slots.size())) {
    Slot& slot = *pool->slots[slot_id];
    slot.active = false;
    slot.finished = false;
    slot.result = SearchResult();
  }
}

}  // extern "C"
}  // namespace fc
