package ml.mxnet_tpu

// GENERATED by tools/gen_scala_ops.py — do not edit by hand.
// Typed creator per registered operator (reference
// scala-package Symbol.scala generated surface). Regenerate:
//   python tools/gen_scala_ops.py
object SymbolOpsGen {
  private def mk(op: String, name: String,
                 params: Seq[(String, String)],
                 inputs: Seq[(String, Symbol)]): Symbol =
    Symbol.create(op, params.toMap, name, inputs: _*)

  def normal(shape: Array[Int],
      name: String = "",
      loc: Float = 0.0f,
      scale: Float = 1.0f): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("loc", loc.toString))
    ps += (("scale", scale.toString))
    ps += (("shape", "(" + shape.mkString(", ") + ")"))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    mk("normal", name, ps.toSeq, ins.toSeq)
  }

  def uniform(shape: Array[Int],
      name: String = "",
      low: Float = 0.0f,
      high: Float = 1.0f): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("low", low.toString))
    ps += (("high", high.toString))
    ps += (("shape", "(" + shape.mkString(", ") + ")"))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    mk("uniform", name, ps.toSeq, ins.toSeq)
  }

  def abs(data: Symbol,
      name: String = ""): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("abs", name, ps.toSeq, ins.toSeq)
  }

  def Activation(data: Symbol,
      actType: String,
      name: String = ""): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("act_type", actType.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("Activation", name, ps.toSeq, ins.toSeq)
  }

  def ElementWiseSum(inputs: Seq[Symbol],
      name: String = ""): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("num_args", inputs.length.toString))
    val ins = inputs.zipWithIndex.map { case (s, i) => ("arg" + i, s) }
    mk("ElementWiseSum", name, ps.toSeq, ins.toSeq)
  }

  def argmax_channel(data: Symbol,
      name: String = ""): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("argmax_channel", name, ps.toSeq, ins.toSeq)
  }

  def batch_dot(lhs: Symbol,
      name: String = "",
      rhs: Symbol = null,
      transposeA: Boolean = false,
      transposeB: Boolean = false): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("transpose_a", transposeA.toString))
    ps += (("transpose_b", transposeB.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("lhs", lhs))
    if (rhs != null) ins += (("rhs", rhs))
    mk("batch_dot", name, ps.toSeq, ins.toSeq)
  }

  def BatchNorm(data: Symbol,
      name: String = "",
      gamma: Symbol = null,
      beta: Symbol = null,
      eps: Float = 0.001f,
      momentum: Float = 0.9f,
      fixGamma: Boolean = true,
      useGlobalStats: Boolean = false,
      axis: Int = 1): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("eps", eps.toString))
    ps += (("momentum", momentum.toString))
    ps += (("fix_gamma", fixGamma.toString))
    ps += (("use_global_stats", useGlobalStats.toString))
    ps += (("axis", axis.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    if (gamma != null) ins += (("gamma", gamma))
    if (beta != null) ins += (("beta", beta))
    mk("BatchNorm", name, ps.toSeq, ins.toSeq)
  }

  def BlockGrad(data: Symbol,
      name: String = ""): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("BlockGrad", name, ps.toSeq, ins.toSeq)
  }

  def broadcast_axis(data: Symbol,
      name: String = "",
      axis: Array[Int] = Array(),
      size: Array[Int] = Array()): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("axis", "(" + axis.mkString(", ") + ")"))
    ps += (("size", "(" + size.mkString(", ") + ")"))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("broadcast_axis", name, ps.toSeq, ins.toSeq)
  }

  def broadcast_div(lhs: Symbol,
      name: String = "",
      rhs: Symbol = null): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("lhs", lhs))
    if (rhs != null) ins += (("rhs", rhs))
    mk("broadcast_div", name, ps.toSeq, ins.toSeq)
  }

  def broadcast_minus(lhs: Symbol,
      name: String = "",
      rhs: Symbol = null): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("lhs", lhs))
    if (rhs != null) ins += (("rhs", rhs))
    mk("broadcast_minus", name, ps.toSeq, ins.toSeq)
  }

  def broadcast_mul(lhs: Symbol,
      name: String = "",
      rhs: Symbol = null): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("lhs", lhs))
    if (rhs != null) ins += (("rhs", rhs))
    mk("broadcast_mul", name, ps.toSeq, ins.toSeq)
  }

  def broadcast_plus(lhs: Symbol,
      name: String = "",
      rhs: Symbol = null): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("lhs", lhs))
    if (rhs != null) ins += (("rhs", rhs))
    mk("broadcast_plus", name, ps.toSeq, ins.toSeq)
  }

  def broadcast_power(lhs: Symbol,
      name: String = "",
      rhs: Symbol = null): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("lhs", lhs))
    if (rhs != null) ins += (("rhs", rhs))
    mk("broadcast_power", name, ps.toSeq, ins.toSeq)
  }

  def CaffeLoss(data: Symbol,
      name: String = "",
      label: Symbol = null,
      prototxt: String = "layer{}",
      numData: Int = 2,
      numOut: Int = 1,
      gradScale: Float = 1.0f): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("prototxt", prototxt.toString))
    ps += (("num_data", numData.toString))
    ps += (("num_out", numOut.toString))
    ps += (("grad_scale", gradScale.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    if (label != null) ins += (("label", label))
    mk("CaffeLoss", name, ps.toSeq, ins.toSeq)
  }

  def CaffeOp(data0: Symbol,
      name: String = "",
      prototxt: String = "layer{}",
      numData: Int = 1,
      numWeight: Int = 0,
      numOut: Int = 1): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("prototxt", prototxt.toString))
    ps += (("num_data", numData.toString))
    ps += (("num_weight", numWeight.toString))
    ps += (("num_out", numOut.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data_0", data0))
    mk("CaffeOp", name, ps.toSeq, ins.toSeq)
  }

  def Cast(data: Symbol,
      dtype: String,
      name: String = ""): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("dtype", dtype.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("Cast", name, ps.toSeq, ins.toSeq)
  }

  def CausalAttention(query: Symbol,
      numHeads: Int,
      numKvHeads: Int,
      headDim: Int,
      seqLen: Int,
      name: String = "",
      key: Symbol = null,
      value: Symbol = null,
      rotary: Boolean = true,
      ropeTheta: Float = 10000.0f,
      rotaryDim: Int = 0,
      valueDim: Int = 0,
      window: Int = 0,
      ropeFactor: Float = 1.0f,
      ropeOriginalPositions: Int = 0,
      ropeBetaFast: Float = 32.0f,
      ropeBetaSlow: Float = 1.0f,
      ropeAttentionFactor: Float = 0.0f): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("rotary", rotary.toString))
    ps += (("rope_theta", ropeTheta.toString))
    ps += (("rotary_dim", rotaryDim.toString))
    ps += (("value_dim", valueDim.toString))
    ps += (("window", window.toString))
    ps += (("rope_factor", ropeFactor.toString))
    ps += (("rope_original_positions", ropeOriginalPositions.toString))
    ps += (("rope_beta_fast", ropeBetaFast.toString))
    ps += (("rope_beta_slow", ropeBetaSlow.toString))
    ps += (("rope_attention_factor", ropeAttentionFactor.toString))
    ps += (("num_heads", numHeads.toString))
    ps += (("num_kv_heads", numKvHeads.toString))
    ps += (("head_dim", headDim.toString))
    ps += (("seq_len", seqLen.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("query", query))
    if (key != null) ins += (("key", key))
    if (value != null) ins += (("value", value))
    mk("CausalAttention", name, ps.toSeq, ins.toSeq)
  }

  def CausalConv1D(data: Symbol,
      kernel: Int,
      seqLen: Int,
      name: String = "",
      weight: Symbol = null,
      bias: Symbol = null,
      noBias: Boolean = false): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("no_bias", noBias.toString))
    ps += (("kernel", kernel.toString))
    ps += (("seq_len", seqLen.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    if (weight != null) ins += (("weight", weight))
    if (bias != null) ins += (("bias", bias))
    mk("CausalConv1D", name, ps.toSeq, ins.toSeq)
  }

  def ceil(data: Symbol,
      name: String = ""): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("ceil", name, ps.toSeq, ins.toSeq)
  }

  def clip(data: Symbol,
      aMin: Float,
      aMax: Float,
      name: String = ""): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("a_min", aMin.toString))
    ps += (("a_max", aMax.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("clip", name, ps.toSeq, ins.toSeq)
  }

  def Concat(inputs: Seq[Symbol],
      name: String = "",
      dim: Int = 1): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("num_args", inputs.length.toString))
    ps += (("dim", dim.toString))
    val ins = inputs.zipWithIndex.map { case (s, i) => ("arg" + i, s) }
    mk("Concat", name, ps.toSeq, ins.toSeq)
  }

  def Convolution(data: Symbol,
      kernel: Array[Int],
      numFilter: Int,
      name: String = "",
      weight: Symbol = null,
      bias: Symbol = null,
      stride: Array[Int] = null,
      pad: Array[Int] = null,
      dilate: Array[Int] = null,
      numGroup: Int = 1,
      noBias: Boolean = false,
      workspace: Int = 512,
      cudnnTune: String = null,
      layout: String = null): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    if (stride != null) ps += (("stride", "(" + stride.mkString(", ") + ")"))
    if (pad != null) ps += (("pad", "(" + pad.mkString(", ") + ")"))
    if (dilate != null) ps += (("dilate", "(" + dilate.mkString(", ") + ")"))
    ps += (("num_group", numGroup.toString))
    ps += (("no_bias", noBias.toString))
    ps += (("workspace", workspace.toString))
    if (cudnnTune != null) ps += (("cudnn_tune", cudnnTune.toString))
    if (layout != null) ps += (("layout", layout.toString))
    ps += (("kernel", "(" + kernel.mkString(", ") + ")"))
    ps += (("num_filter", numFilter.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    if (weight != null) ins += (("weight", weight))
    if (bias != null) ins += (("bias", bias))
    mk("Convolution", name, ps.toSeq, ins.toSeq)
  }

  def Correlation(data1: Symbol,
      name: String = "",
      data2: Symbol = null,
      kernelSize: Int = 1,
      maxDisplacement: Int = 1,
      stride1: Int = 1,
      stride2: Int = 1,
      padSize: Int = 0,
      isMultiply: Boolean = true): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("kernel_size", kernelSize.toString))
    ps += (("max_displacement", maxDisplacement.toString))
    ps += (("stride1", stride1.toString))
    ps += (("stride2", stride2.toString))
    ps += (("pad_size", padSize.toString))
    ps += (("is_multiply", isMultiply.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data1", data1))
    if (data2 != null) ins += (("data2", data2))
    mk("Correlation", name, ps.toSeq, ins.toSeq)
  }

  def cos(data: Symbol,
      name: String = ""): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("cos", name, ps.toSeq, ins.toSeq)
  }

  def Crop(inputs: Seq[Symbol],
      name: String = "",
      numArgs: Int = 1,
      offset: Array[Int] = Array(0, 0),
      hW: Array[Int] = Array(0, 0),
      centerCrop: Boolean = false,
      begin: Array[Int] = null,
      end: Array[Int] = null): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("num_args", inputs.length.toString))
    ps += (("num_args", numArgs.toString))
    ps += (("offset", "(" + offset.mkString(", ") + ")"))
    ps += (("h_w", "(" + hW.mkString(", ") + ")"))
    ps += (("center_crop", centerCrop.toString))
    if (begin != null) ps += (("begin", "(" + begin.mkString(", ") + ")"))
    if (end != null) ps += (("end", "(" + end.mkString(", ") + ")"))
    val ins = inputs.zipWithIndex.map { case (s, i) => ("arg" + i, s) }
    mk("Crop", name, ps.toSeq, ins.toSeq)
  }

  def CTCLoss(data: Symbol,
      name: String = "",
      label: Symbol = null): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    if (label != null) ins += (("label", label))
    mk("CTCLoss", name, ps.toSeq, ins.toSeq)
  }

  def Custom(data: Symbol,
      opType: String,
      name: String = ""): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("op_type", opType.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("Custom", name, ps.toSeq, ins.toSeq)
  }

  def Deconvolution(data: Symbol,
      kernel: Array[Int],
      numFilter: Int,
      name: String = "",
      weight: Symbol = null,
      bias: Symbol = null,
      stride: Array[Int] = null,
      pad: Array[Int] = null,
      dilate: Array[Int] = null,
      numGroup: Int = 1,
      noBias: Boolean = false,
      workspace: Int = 512,
      cudnnTune: String = null,
      layout: String = null): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    if (stride != null) ps += (("stride", "(" + stride.mkString(", ") + ")"))
    if (pad != null) ps += (("pad", "(" + pad.mkString(", ") + ")"))
    if (dilate != null) ps += (("dilate", "(" + dilate.mkString(", ") + ")"))
    ps += (("num_group", numGroup.toString))
    ps += (("no_bias", noBias.toString))
    ps += (("workspace", workspace.toString))
    if (cudnnTune != null) ps += (("cudnn_tune", cudnnTune.toString))
    if (layout != null) ps += (("layout", layout.toString))
    ps += (("kernel", "(" + kernel.mkString(", ") + ")"))
    ps += (("num_filter", numFilter.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    if (weight != null) ins += (("weight", weight))
    if (bias != null) ins += (("bias", bias))
    mk("Deconvolution", name, ps.toSeq, ins.toSeq)
  }

  def dot(lhs: Symbol,
      name: String = "",
      rhs: Symbol = null,
      transposeA: Boolean = false,
      transposeB: Boolean = false): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("transpose_a", transposeA.toString))
    ps += (("transpose_b", transposeB.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("lhs", lhs))
    if (rhs != null) ins += (("rhs", rhs))
    mk("dot", name, ps.toSeq, ins.toSeq)
  }

  def Dropout(data: Symbol,
      name: String = "",
      p: Float = 0.5f): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("p", p.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("Dropout", name, ps.toSeq, ins.toSeq)
  }

  def element_mask(lhs: Symbol,
      name: String = "",
      rhs: Symbol = null): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("lhs", lhs))
    if (rhs != null) ins += (("rhs", rhs))
    mk("element_mask", name, ps.toSeq, ins.toSeq)
  }

  def Embedding(data: Symbol,
      inputDim: Int,
      outputDim: Int,
      name: String = "",
      weight: Symbol = null): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("input_dim", inputDim.toString))
    ps += (("output_dim", outputDim.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    if (weight != null) ins += (("weight", weight))
    mk("Embedding", name, ps.toSeq, ins.toSeq)
  }

  def exp(data: Symbol,
      name: String = ""): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("exp", name, ps.toSeq, ins.toSeq)
  }

  def expand_dims(data: Symbol,
      axis: Int,
      name: String = ""): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("axis", axis.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("expand_dims", name, ps.toSeq, ins.toSeq)
  }

  def Flatten(data: Symbol,
      name: String = ""): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("Flatten", name, ps.toSeq, ins.toSeq)
  }

  def Flip(data: Symbol,
      axis: Int,
      name: String = ""): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("axis", axis.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("Flip", name, ps.toSeq, ins.toSeq)
  }

  def floor(data: Symbol,
      name: String = ""): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("floor", name, ps.toSeq, ins.toSeq)
  }

  def FullyConnected(data: Symbol,
      numHidden: Int,
      name: String = "",
      weight: Symbol = null,
      bias: Symbol = null,
      noBias: Boolean = false): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("no_bias", noBias.toString))
    ps += (("num_hidden", numHidden.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    if (weight != null) ins += (("weight", weight))
    if (bias != null) ins += (("bias", bias))
    mk("FullyConnected", name, ps.toSeq, ins.toSeq)
  }

  def GatedDeltaRule(query: Symbol,
      numHeads: Int,
      keyDim: Int,
      valueDim: Int,
      seqLen: Int,
      name: String = "",
      key: Symbol = null,
      value: Symbol = null,
      a: Symbol = null,
      b: Symbol = null,
      ALog: Symbol = null,
      dtBias: Symbol = null,
      numKeyHeads: Int = 0,
      chunk: Int = 64,
      negEigval: Boolean = false,
      gateFloor: Float = 0.0f): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("num_key_heads", numKeyHeads.toString))
    ps += (("chunk", chunk.toString))
    ps += (("neg_eigval", negEigval.toString))
    ps += (("gate_floor", gateFloor.toString))
    ps += (("num_heads", numHeads.toString))
    ps += (("key_dim", keyDim.toString))
    ps += (("value_dim", valueDim.toString))
    ps += (("seq_len", seqLen.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("query", query))
    if (key != null) ins += (("key", key))
    if (value != null) ins += (("value", value))
    if (a != null) ins += (("a", a))
    if (b != null) ins += (("b", b))
    if (ALog != null) ins += (("A_log", ALog))
    if (dtBias != null) ins += (("dt_bias", dtBias))
    mk("GatedDeltaRule", name, ps.toSeq, ins.toSeq)
  }

  def GatedShortConv(data: Symbol,
      kernel: Int,
      seqLen: Int,
      name: String = "",
      weight: Symbol = null): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("kernel", kernel.toString))
    ps += (("seq_len", seqLen.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    if (weight != null) ins += (("weight", weight))
    mk("GatedShortConv", name, ps.toSeq, ins.toSeq)
  }

  def IdentityAttachKLSparseReg(data: Symbol,
      name: String = "",
      sparsenessTarget: Float = 0.1f,
      penalty: Float = 0.001f,
      momentum: Float = 0.9f): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("sparseness_target", sparsenessTarget.toString))
    ps += (("penalty", penalty.toString))
    ps += (("momentum", momentum.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("IdentityAttachKLSparseReg", name, ps.toSeq, ins.toSeq)
  }

  def L2Normalization(data: Symbol,
      name: String = "",
      eps: Float = 1e-10f,
      mode: String = "instance"): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("eps", eps.toString))
    ps += (("mode", mode.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("L2Normalization", name, ps.toSeq, ins.toSeq)
  }

  def LeakyReLU(data: Symbol,
      name: String = "",
      actType: String = "leaky",
      slope: Float = 0.25f,
      lowerBound: Float = 0.125f,
      upperBound: Float = 0.334f): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("act_type", actType.toString))
    ps += (("slope", slope.toString))
    ps += (("lower_bound", lowerBound.toString))
    ps += (("upper_bound", upperBound.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("LeakyReLU", name, ps.toSeq, ins.toSeq)
  }

  def LinearRegressionOutput(data: Symbol,
      name: String = "",
      label: Symbol = null,
      gradScale: Float = 1.0f): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("grad_scale", gradScale.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    if (label != null) ins += (("label", label))
    mk("LinearRegressionOutput", name, ps.toSeq, ins.toSeq)
  }

  def log(data: Symbol,
      name: String = ""): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("log", name, ps.toSeq, ins.toSeq)
  }

  def LogisticRegressionOutput(data: Symbol,
      name: String = "",
      label: Symbol = null,
      gradScale: Float = 1.0f): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("grad_scale", gradScale.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    if (label != null) ins += (("label", label))
    mk("LogisticRegressionOutput", name, ps.toSeq, ins.toSeq)
  }

  def LRN(data: Symbol,
      nsize: Int,
      name: String = "",
      alpha: Float = 0.0001f,
      beta: Float = 0.75f,
      knorm: Float = 2.0f): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("alpha", alpha.toString))
    ps += (("beta", beta.toString))
    ps += (("knorm", knorm.toString))
    ps += (("nsize", nsize.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("LRN", name, ps.toSeq, ins.toSeq)
  }

  def MAERegressionOutput(data: Symbol,
      name: String = "",
      label: Symbol = null,
      gradScale: Float = 1.0f): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("grad_scale", gradScale.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    if (label != null) ins += (("label", label))
    mk("MAERegressionOutput", name, ps.toSeq, ins.toSeq)
  }

  def MakeLoss(data: Symbol,
      name: String = "",
      gradScale: Float = 1.0f): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("grad_scale", gradScale.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("MakeLoss", name, ps.toSeq, ins.toSeq)
  }

  def max(data: Symbol,
      name: String = "",
      axis: Array[Int] = null,
      keepdims: Boolean = false): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    if (axis != null) ps += (("axis", "(" + axis.mkString(", ") + ")"))
    ps += (("keepdims", keepdims.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("max", name, ps.toSeq, ins.toSeq)
  }

  def min(data: Symbol,
      name: String = "",
      axis: Array[Int] = null,
      keepdims: Boolean = false): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    if (axis != null) ps += (("axis", "(" + axis.mkString(", ") + ")"))
    ps += (("keepdims", keepdims.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("min", name, ps.toSeq, ins.toSeq)
  }

  def negative(data: Symbol,
      name: String = ""): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("negative", name, ps.toSeq, ins.toSeq)
  }

  def Pooling(data: Symbol,
      kernel: Array[Int],
      name: String = "",
      poolType: String = "max",
      stride: Array[Int] = null,
      pad: Array[Int] = null,
      globalPool: Boolean = false,
      layout: String = null): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("pool_type", poolType.toString))
    if (stride != null) ps += (("stride", "(" + stride.mkString(", ") + ")"))
    if (pad != null) ps += (("pad", "(" + pad.mkString(", ") + ")"))
    ps += (("global_pool", globalPool.toString))
    if (layout != null) ps += (("layout", layout.toString))
    ps += (("kernel", "(" + kernel.mkString(", ") + ")"))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("Pooling", name, ps.toSeq, ins.toSeq)
  }

  def Reshape(data: Symbol,
      name: String = "",
      shape: Array[Int] = null,
      targetShape: Array[Int] = null,
      reverse: Boolean = false): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    if (shape != null) ps += (("shape", "(" + shape.mkString(", ") + ")"))
    if (targetShape != null) ps += (("target_shape", "(" + targetShape.mkString(", ") + ")"))
    ps += (("reverse", reverse.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("Reshape", name, ps.toSeq, ins.toSeq)
  }

  def RMSNorm(data: Symbol,
      name: String = "",
      gamma: Symbol = null,
      eps: Float = 1e-05f,
      numGroups: Int = 1,
      gated: Boolean = false,
      gateAfter: Boolean = false,
      sharedGamma: Boolean = false): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("eps", eps.toString))
    ps += (("num_groups", numGroups.toString))
    ps += (("gated", gated.toString))
    ps += (("gate_after", gateAfter.toString))
    ps += (("shared_gamma", sharedGamma.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    if (gamma != null) ins += (("gamma", gamma))
    mk("RMSNorm", name, ps.toSeq, ins.toSeq)
  }

  def RNN(data: Symbol,
      stateSize: Int,
      numLayers: Int,
      mode: String,
      name: String = "",
      parameters: Symbol = null,
      state: Symbol = null,
      stateCell: Symbol = null,
      bidirectional: Boolean = false,
      p: Float = 0.0f,
      stateOutputs: Boolean = false): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("bidirectional", bidirectional.toString))
    ps += (("p", p.toString))
    ps += (("state_outputs", stateOutputs.toString))
    ps += (("state_size", stateSize.toString))
    ps += (("num_layers", numLayers.toString))
    ps += (("mode", mode.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    if (parameters != null) ins += (("parameters", parameters))
    if (state != null) ins += (("state", state))
    if (stateCell != null) ins += (("state_cell", stateCell))
    mk("RNN", name, ps.toSeq, ins.toSeq)
  }

  def ROIPooling(data: Symbol,
      pooledSize: Array[Int],
      spatialScale: Float,
      name: String = "",
      rois: Symbol = null): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("pooled_size", "(" + pooledSize.mkString(", ") + ")"))
    ps += (("spatial_scale", spatialScale.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    if (rois != null) ins += (("rois", rois))
    mk("ROIPooling", name, ps.toSeq, ins.toSeq)
  }

  def round(data: Symbol,
      name: String = ""): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("round", name, ps.toSeq, ins.toSeq)
  }

  def RoutedExperts(data: Symbol,
      numExperts: Int,
      numHeld: Int,
      topK: Int,
      numHidden: Int,
      name: String = "",
      routerWeight: Symbol = null,
      upWeight: Symbol = null,
      downWeight: Symbol = null,
      firstHeld: Int = 0,
      scale: Float = 1.0f,
      biasUpdateRate: Float = 0.0f,
      gated: Boolean = false,
      normEps: Float = 1e-20f,
      nGroup: Int = 1,
      topkGroup: Int = 1,
      scoreFunc: String = "sigmoid",
      auxLossCoef: Float = 0.0f): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("first_held", firstHeld.toString))
    ps += (("scale", scale.toString))
    ps += (("bias_update_rate", biasUpdateRate.toString))
    ps += (("gated", gated.toString))
    ps += (("norm_eps", normEps.toString))
    ps += (("n_group", nGroup.toString))
    ps += (("topk_group", topkGroup.toString))
    ps += (("score_func", scoreFunc.toString))
    ps += (("aux_loss_coef", auxLossCoef.toString))
    ps += (("num_experts", numExperts.toString))
    ps += (("num_held", numHeld.toString))
    ps += (("top_k", topK.toString))
    ps += (("num_hidden", numHidden.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    if (routerWeight != null) ins += (("router_weight", routerWeight))
    if (upWeight != null) ins += (("up_weight", upWeight))
    if (downWeight != null) ins += (("down_weight", downWeight))
    mk("RoutedExperts", name, ps.toSeq, ins.toSeq)
  }

  def rsqrt(data: Symbol,
      name: String = ""): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("rsqrt", name, ps.toSeq, ins.toSeq)
  }

  def SequenceLast(data: Symbol,
      name: String = "",
      useSequenceLength: Boolean = false): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("use_sequence_length", useSequenceLength.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("SequenceLast", name, ps.toSeq, ins.toSeq)
  }

  def SequenceMask(data: Symbol,
      name: String = "",
      useSequenceLength: Boolean = false,
      value: Float = 0.0f): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("use_sequence_length", useSequenceLength.toString))
    ps += (("value", value.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("SequenceMask", name, ps.toSeq, ins.toSeq)
  }

  def SequenceReverse(data: Symbol,
      name: String = "",
      useSequenceLength: Boolean = false): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("use_sequence_length", useSequenceLength.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("SequenceReverse", name, ps.toSeq, ins.toSeq)
  }

  def sign(data: Symbol,
      name: String = ""): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("sign", name, ps.toSeq, ins.toSeq)
  }

  def sin(data: Symbol,
      name: String = ""): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("sin", name, ps.toSeq, ins.toSeq)
  }

  def slice_axis(data: Symbol,
      axis: Int,
      begin: Int,
      end: Int,
      name: String = ""): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("axis", axis.toString))
    ps += (("begin", begin.toString))
    ps += (("end", end.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("slice_axis", name, ps.toSeq, ins.toSeq)
  }

  def SliceChannel(data: Symbol,
      numOutputs: Int,
      name: String = "",
      axis: Int = 1,
      squeezeAxis: Boolean = false): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("axis", axis.toString))
    ps += (("squeeze_axis", squeezeAxis.toString))
    ps += (("num_outputs", numOutputs.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("SliceChannel", name, ps.toSeq, ins.toSeq)
  }

  def smooth_l1(data: Symbol,
      name: String = "",
      scalar: Float = 1.0f): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("scalar", scalar.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("smooth_l1", name, ps.toSeq, ins.toSeq)
  }

  def SoftmaxOutput(data: Symbol,
      name: String = "",
      label: Symbol = null,
      gradScale: Float = 1.0f,
      ignoreLabel: Float = -1.0f,
      multiOutput: Boolean = false,
      useIgnore: Boolean = false,
      preserveShape: Boolean = false,
      normalization: String = "null"): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("grad_scale", gradScale.toString))
    ps += (("ignore_label", ignoreLabel.toString))
    ps += (("multi_output", multiOutput.toString))
    ps += (("use_ignore", useIgnore.toString))
    ps += (("preserve_shape", preserveShape.toString))
    ps += (("normalization", normalization.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    if (label != null) ins += (("label", label))
    mk("SoftmaxOutput", name, ps.toSeq, ins.toSeq)
  }

  def softmax_cross_entropy(data: Symbol,
      name: String = "",
      label: Symbol = null): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    if (label != null) ins += (("label", label))
    mk("softmax_cross_entropy", name, ps.toSeq, ins.toSeq)
  }

  def SoftmaxActivation(data: Symbol,
      name: String = "",
      mode: String = "instance"): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("mode", mode.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("SoftmaxActivation", name, ps.toSeq, ins.toSeq)
  }

  def SpatialTransformer(data: Symbol,
      targetShape: Array[Int],
      name: String = "",
      loc: Symbol = null,
      transformType: String = "affine",
      samplerType: String = "bilinear"): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("transform_type", transformType.toString))
    ps += (("sampler_type", samplerType.toString))
    ps += (("target_shape", "(" + targetShape.mkString(", ") + ")"))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    if (loc != null) ins += (("loc", loc))
    mk("SpatialTransformer", name, ps.toSeq, ins.toSeq)
  }

  def sqrt(data: Symbol,
      name: String = ""): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("sqrt", name, ps.toSeq, ins.toSeq)
  }

  def square(data: Symbol,
      name: String = ""): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("square", name, ps.toSeq, ins.toSeq)
  }

  def SSMScan(data: Symbol,
      numHeads: Int,
      headDim: Int,
      numGroups: Int,
      stateSize: Int,
      seqLen: Int,
      name: String = "",
      dt: Symbol = null,
      ALog: Symbol = null,
      D: Symbol = null,
      dtBias: Symbol = null,
      chunk: Int = 128): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("chunk", chunk.toString))
    ps += (("num_heads", numHeads.toString))
    ps += (("head_dim", headDim.toString))
    ps += (("num_groups", numGroups.toString))
    ps += (("state_size", stateSize.toString))
    ps += (("seq_len", seqLen.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    if (dt != null) ins += (("dt", dt))
    if (ALog != null) ins += (("A_log", ALog))
    if (D != null) ins += (("D", D))
    if (dtBias != null) ins += (("dt_bias", dtBias))
    mk("SSMScan", name, ps.toSeq, ins.toSeq)
  }

  def sum(data: Symbol,
      name: String = "",
      axis: Array[Int] = null,
      keepdims: Boolean = false): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    if (axis != null) ps += (("axis", "(" + axis.mkString(", ") + ")"))
    ps += (("keepdims", keepdims.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("sum", name, ps.toSeq, ins.toSeq)
  }

  def SVMOutput(data: Symbol,
      name: String = "",
      label: Symbol = null,
      margin: Float = 1.0f,
      regularizationCoefficient: Float = 1.0f,
      useLinear: Boolean = false): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("margin", margin.toString))
    ps += (("regularization_coefficient", regularizationCoefficient.toString))
    ps += (("use_linear", useLinear.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    if (label != null) ins += (("label", label))
    mk("SVMOutput", name, ps.toSeq, ins.toSeq)
  }

  def SwapAxis(data: Symbol,
      name: String = "",
      dim1: Int = 0,
      dim2: Int = 0): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("dim1", dim1.toString))
    ps += (("dim2", dim2.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("SwapAxis", name, ps.toSeq, ins.toSeq)
  }

  def transpose(data: Symbol,
      name: String = "",
      axes: Array[Int] = null): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    if (axes != null) ps += (("axes", "(" + axes.mkString(", ") + ")"))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    mk("transpose", name, ps.toSeq, ins.toSeq)
  }

  def UpSampling(inputs: Seq[Symbol],
      scale: Int,
      name: String = "",
      sampleType: String = "nearest",
      numArgs: Int = 1): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("num_args", inputs.length.toString))
    ps += (("sample_type", sampleType.toString))
    ps += (("num_args", numArgs.toString))
    ps += (("scale", scale.toString))
    val ins = inputs.zipWithIndex.map { case (s, i) => ("arg" + i, s) }
    mk("UpSampling", name, ps.toSeq, ins.toSeq)
  }

  def WarpCTC(data: Symbol,
      name: String = "",
      label: Symbol = null,
      labelLength: Int = 0,
      inputLength: Int = 0): Symbol = {
    val ps = scala.collection.mutable.ArrayBuffer[(String, String)]()
    ps += (("label_length", labelLength.toString))
    ps += (("input_length", inputLength.toString))
    val ins = scala.collection.mutable.ArrayBuffer[(String, Symbol)]()
    ins += (("data", data))
    if (label != null) ins += (("label", label))
    mk("WarpCTC", name, ps.toSeq, ins.toSeq)
  }

}
